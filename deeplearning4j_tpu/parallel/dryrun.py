"""Multi-chip sharding dryrun: one full explicit-SPMD train step on tiny
shapes over an n-device mesh (dp/pp/tp/sp — GPipe micro-batch pipeline over
pp, Megatron-style tp, ring attention over sp, dp gradient pmean).

Package home of the logic behind the repo-root ``__graft_entry__.py``
driver hook and the ``python -m deeplearning4j_tpu dryrun`` CLI: both
import from here, so the check works from an installed package too.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def mesh_spec_for(n_devices: int):
    """Factor n into (dp, pp, tp, sp): peel 2s round-robin so every
    parallelism kind is exercised when n allows (8 -> dp2·pp2·tp2,
    16 -> + sp2).  ep is exercised by the sharded-embedding path
    (tests/test_sharded_embedding.py) rather than the flagship step."""
    from .mesh import DP, PP, SP, TP, MeshSpec
    dims = {DP: 1, PP: 1, TP: 1, SP: 1}
    order = [DP, PP, TP, SP]
    n, i = n_devices, 0
    while n % 2 == 0 and n > 1:
        dims[order[i % 4]] *= 2
        n //= 2
        i += 1
    dims[DP] *= n  # odd residue onto dp
    return MeshSpec(dp=dims[DP], sp=dims[SP], tp=dims[TP],
                    pp=dims[PP], ep=1)


def dryrun_multichip(n_devices: int) -> None:
    """One full sharded train step on tiny shapes over n virtual devices.

    A virtual-CPU-mesh check: it pins the CPU platform in-process and runs
    on the forced host device pool.
    """
    jax.config.update("jax_platforms", "cpu")
    assert len(jax.devices()) >= n_devices, (
        f"need {n_devices} devices, have {len(jax.devices())} "
        "(set XLA_FLAGS=--xla_force_host_platform_device_count)")

    from ..models.transformer import TransformerConfig, TransformerLM
    from ..optimize import transforms as T
    from .mesh import DP, PP, SP, TP, make_mesh

    spec = mesh_spec_for(n_devices)
    mesh = make_mesh(spec, devices=jax.devices()[:n_devices])

    sizes = spec.resolve(n_devices)
    n_heads = max(4, sizes[TP] * 2)
    seq = 8 * sizes[SP]
    n_micro = 2 * sizes[PP]
    batch = sizes[DP] * n_micro      # local batch per dp shard == n_micro
    cfg = TransformerConfig(
        vocab_size=128, d_model=8 * n_heads, n_heads=n_heads,
        n_layers=2 * sizes[PP], d_ff=64, max_len=seq, causal=True,
        dtype=jnp.float32, remat=True,
    )

    if sizes[PP] > 1:
        from ..models.pipeline import PipelinedTransformerLM
        model = PipelinedTransformerLM(cfg, mesh, n_micro=n_micro)
    else:
        model = TransformerLM(cfg, mesh=mesh)
    tx = T.adamw(T.warmup_cosine(1e-2, 2, 100), weight_decay=0.01)
    params = model.place(model.init(jax.random.key(0)))
    opt = model.init_opt(params, tx)
    tokens = jax.random.randint(jax.random.key(1), (batch, seq), 0, cfg.vocab_size)
    targets = jnp.roll(tokens, -1, axis=1)

    step = model.build_train_step(tx)
    params, _, loss = step(params, opt, tokens, targets)
    loss = float(loss)
    assert jnp.isfinite(loss), f"non-finite loss {loss}"

    # second leg: ZeRO-1 weight-update sharding ON the same mesh — when pp
    # is live this exercises the pipelined ZeRO-1 path (dp-sharded state
    # with a pp row dimension on stage-sharded leaves).
    z1 = ""
    if sizes[DP] > 1:
        p1 = model.place(model.init(jax.random.key(0)))  # step donated params
        o1 = model.init_opt_zero1(p1, tx)
        z1_step = model.build_train_step(tx, zero1=True)
        _, _, z1_loss = z1_step(p1, o1, tokens, targets)
        z1_loss = float(z1_loss)
        assert jnp.isfinite(z1_loss), f"non-finite zero1 loss {z1_loss}"
        kind = "pp-pipelined" if sizes[PP] > 1 else "plain"
        z1 = f" zero1[{kind},dp{sizes[DP]}]_loss={z1_loss:.4f}"

    # third leg: cross-device ring attention.  The round-robin factoring
    # gives sp=1 at n=8 (dp2·pp2·tp2), so ring attention's ppermute path
    # would only ever run over sp>1 at n>=16.  Fold pp into sp (same device
    # count) so the driver-recorded dryrun exercises the ring at n=8 too.
    sp = ""
    if sizes[SP] == 1 and sizes[PP] > 1:
        from .mesh import MeshSpec
        sp_spec = MeshSpec(dp=sizes[DP], sp=sizes[PP] * sizes[SP],
                           tp=sizes[TP], pp=1, ep=1)
        sp_mesh = make_mesh(sp_spec, devices=jax.devices()[:n_devices])
        sp_seq = 8 * sp_spec.sp
        sp_cfg = TransformerConfig(
            vocab_size=128, d_model=8 * n_heads, n_heads=n_heads,
            n_layers=2, d_ff=64, max_len=sp_seq, causal=True,
            dtype=jnp.float32, remat=True,
        )
        sp_model = TransformerLM(sp_cfg, mesh=sp_mesh)
        p2 = sp_model.place(sp_model.init(jax.random.key(0)))
        o2 = sp_model.init_opt(p2, tx)
        sp_tokens = jax.random.randint(
            jax.random.key(2), (sizes[DP] * 2, sp_seq), 0, sp_cfg.vocab_size)
        sp_step = sp_model.build_train_step(tx)
        _, _, sp_loss = sp_step(p2, o2, sp_tokens, jnp.roll(sp_tokens, -1, axis=1))
        sp_loss = float(sp_loss)
        assert jnp.isfinite(sp_loss), f"non-finite sp loss {sp_loss}"
        sp = f" ring[dp{sp_spec.dp}·tp{sp_spec.tp}·sp{sp_spec.sp}]_loss={sp_loss:.4f}"

    print(f"dryrun_multichip OK: mesh={dict(sizes)} devices={n_devices} "
          f"batch={batch} seq={seq} n_micro={n_micro if sizes[PP] > 1 else 0} "
          f"loss={loss:.4f}{z1}{sp}")
