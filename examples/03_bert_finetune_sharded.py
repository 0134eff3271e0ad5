"""Fine-tune a transformer classifier over an explicit SPMD device mesh.

The BERT-fine-tune north star (BASELINE.json) in miniature: build a
bidirectional transformer, attach a classification head, and run the
AdamW fine-tune step jitted over a (dp, sp, tp) mesh — the same program
shape the framework uses on a TPU pod slice. Here the mesh is 8 virtual
CPU devices so the example runs anywhere; on real hardware only the mesh
construction changes.

Run:  python examples/03_bert_finetune_sharded.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp

from deeplearning4j_tpu.models.transformer import TransformerConfig, TransformerLM
from deeplearning4j_tpu.optimize import transforms as T
from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh


def main():
    cfg = TransformerConfig(vocab_size=256, d_model=64, n_heads=4, n_layers=2,
                            d_ff=128, max_len=32, causal=False,
                            dtype=jnp.float32, remat=False)
    mesh = make_mesh(MeshSpec(dp=2, sp=2, tp=2))
    model = TransformerLM(cfg, mesh=mesh)

    tree = model.place(model.init_finetune(jax.random.key(0), n_classes=2),
                       model.finetune_specs())
    tx = T.adamw(T.warmup_linear(3e-3, 5, 200), weight_decay=0.01)
    opt = model.init_opt(tree, tx)
    step = model.build_finetune_step(tx)

    # synthetic task: does token id 7 appear anywhere in the sequence?
    tokens = jax.random.randint(jax.random.key(3), (32, 32), 0, cfg.vocab_size)
    labels = jnp.any(tokens == 7, axis=1).astype(jnp.int32)

    # async hot loop: losses stay on device — float() every step would
    # stall dispatch; one block_until_ready fence resolves the whole run
    losses = []
    for _ in range(40):
        tree, opt, loss = step(tree, opt, tokens, labels)
        losses.append(loss)
    losses = [float(l) for l in jax.block_until_ready(losses)]
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))}")
    print(f"loss: {losses[0]:.4f} -> {losses[-1]:.4f}")
    assert losses[-1] < losses[0], "fine-tune loss should drop"


if __name__ == "__main__":
    main()
