"""Continuous-batching inference engine (DESIGN.md §13).

Two workloads over one discipline — keep the device batch full, keep the
host off the per-token path:

- :class:`InferenceEngine`: slot-based continuous batching for the
  flagship transformer.  The KV cache is a POOL of ``slots`` rows
  (``(S, max_len, H, Dh)`` per layer); every decode step advances ALL
  occupied slots one token through :func:`decode_step` with per-slot
  positions, new sequences are admitted into free rows between steps
  (prefill on a batch-of-1 cache, then one scatter into the pool), and a
  finished sequence (EOS / length budget) frees its row for the next
  arrival.  Sequences at different depths share every device batch —
  ragged traffic cannot drain the batch the way static batching does.

- :class:`BatchScorer`: batched forward/score for ``MultiLayerNetwork``
  and zoo models — concurrent callers coalesce into one padded
  (power-of-two bucket) device batch through any row-wise ``fn``.

Hot-path rules (PR-2/PR-3 heritage): the decode loop dispatches
``resolve_every`` steps back-to-back under ``hot_loop_guard()`` — zero
host syncs per token — and resolves the emitted-token stack at ONE
``allow_transfers()`` fence per segment, where EOS/length bookkeeping,
admissions, and metrics publication happen.  Every jitted entry donates
the engine state, so the cache pool is updated in place.

RNG parity contract: slot ``s`` runs the exact draw sequence of
``Transformer.sample(..., key=jax.random.key(seed), kv_cache=True)`` —
split once per generated token, sample from the second half — so a
served continuation is token-identical to the offline sampler under the
same seed (the tier-1 acceptance test).

PR-9 memory/latency tier, all OFF by default (DESIGN.md §17):

- ``paged=True``: the dense ``(S, max_len)`` KV rows become fixed-size
  pages in a shared device pool, addressed through per-slot block
  tables (host free-list + refcounts in :class:`~.paging.PagePool`).
  Decode gathers each row's logical K/V into exactly the dense shape
  before the dense attention ops run, so logits stay bitwise.
- ``prefix_cache=True``: a content-addressed cache (chained hash of
  full token pages → pinned pages) admits shared prompt prefixes by
  block-table aliasing — the system-prompt prefill runs once.
- ``speculative=True``: a small draft model proposes ``spec_k`` greedy
  tokens; ONE windowed verify dispatch on the target scores all of
  them, and every emitted token is drawn from TARGET logits with the
  request's exact offline key stream — the draft only decides how MANY
  tokens emit per dispatch, never which, so token parity is preserved
  under greedy and temperature sampling alike.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..analysis.runtime import allow_transfers, hot_loop_guard
from ..analysis.shardguard import SHARDGUARD
from ..models.transformer import (decode_step, decode_step_paged,
                                  decode_window, decode_window_paged,
                                  gather_paged_layer, init_decode_cache,
                                  init_paged_cache, paged_flat_index,
                                  reset_cache_pages, reset_cache_slots,
                                  scatter_paged_layer)
from ..observability import COSTS, FLIGHTREC, METRICS, TENANTS, trace
from ..observability.core import enabled as _obs_enabled
from ..parallel.checkpoint import CheckpointManager
from ..parallel.compile_cache import setup_compile_cache
from ..resilience.faults import FAULTS
from .batcher import (Completion, GenerateRequest, PagePoolExhausted,
                      PendingResult, RequestQueue, ScoreRequest)
from .paging import PagePool

#: unit-interval buckets for fill-ratio histograms (observe_time is the
#: registry's generic histogram feed; these are ratios, not seconds)
FILL_BUCKETS = (0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875, 1.0)


@dataclasses.dataclass(frozen=True)
class ServingConfig:
    """Engine knobs (the model's own shape lives in TransformerConfig)."""

    slots: int = 4                  # concurrent sequences in the device batch
    resolve_every: int = 4          # decode steps dispatched per host fence
    max_queue: int = 64             # RequestQueue bound (429 beyond)
    max_batch_delay_ms: float = 2.0  # idle coalescing window
    min_prefill_bucket: int = 8     # floor of the prompt bucket ladder
    idle_wait_s: float = 0.05       # queue poll period while no slot is live
    default_eos_id: int | None = None
    int8_decode: bool = False       # serve int8 weight-quantized FFN/head
    #                                 (opt-in; adoption gated on token-level
    #                                 top-1 agreement with f32 decode)
    # ---- PR-9 paged/prefix/speculative tier (all default to the dense
    # ---- behavior above; every combination keeps exact token parity)
    paged: bool = False             # page-pool KV instead of dense slot rows
    page_size: int = 16             # tokens per KV page (any size >= 1 works)
    num_pages: int | None = None    # pool capacity; None -> slots*ceil(T/ps)
    prefix_cache: bool = False      # content-addressed prefix sharing (paged)
    speculative: bool = False       # draft-proposes / target-verifies decode
    spec_k: int = 3                 # draft tokens proposed per verify window
    paged_attention_impl: str = "gather"  # "gather" (jnp, bitwise) or a
    #                                 registry candidate name — default off,
    #                                 unmeasured
    kv_quant: str | None = None     # KV-page storage precision (DESIGN.md
    #                                 §20): None = model dtype (bitwise),
    #                                 "int8" = per-page per-head absmax int8
    #                                 (~4x pool capacity, ≥0.999 token top-1
    #                                 agreement), "fp8" = float8 storage on
    #                                 jax builds that have it (gated off by
    #                                 default like every quant tier).
    #                                 Requires paged=True.
    # ---- disaggregated prefill/decode tier (DESIGN.md §27)
    role: str = "unified"           # "unified" (classic colocated engine),
    #                                 "prefill" (prompt prefill only: no
    #                                 serve thread, work arrives through
    #                                 prefill() and leaves as KV pages), or
    #                                 "decode" (a unified engine that also
    #                                 publishes the decode-tier queue gauge
    #                                 and is the admit_from_pages target).
    #                                 "prefill" requires paged=True — the
    #                                 migration unit is a KV page.


def _named(name: str, fn: Callable) -> Callable:
    """``fn`` renamed, so that ``jax.jit`` calls its program ``jit_<name>``
    and a profiler trace shows the engine's programs by what they do."""
    fn.__name__ = fn.__qualname__ = name
    return fn


def kv_page_bytes(mcfg, page_size: int, kv_quant: str | None = None) -> int:
    """Device bytes one KV page costs under the given storage mode — the
    accounting behind ``serving.kv_bytes*`` and the capacity planning in
    ``tools/metrics_dump.py``.  Counts K+V data across all layers at the
    storage itemsize (1 for int8/fp8) plus, when quantized, the per-page
    per-kv-head f32 absmax scales stored beside the pool."""
    from ..ops.pallas import kv_quant as kvq
    kvh = mcfg.kv_heads
    item = kvq.kv_itemsize(kv_quant, mcfg.dtype)
    per_layer = page_size * kvh * mcfg.head_dim * 2 * item
    if kv_quant is not None:
        per_layer += 2 * kvh * 4   # k_scale + v_scale rows, f32
    return per_layer * mcfg.n_layers


class MigrationRejected(RuntimeError):
    """A migrated request could not be admitted into the decode batch
    (weight generation moved between claim and admission, engine
    stopping).  Nothing was corrupted — the decode-side refcounts were
    released and the request should simply be requeued and re-migrated
    (the :class:`~.disagg.DisaggScheduler` does exactly that)."""


@dataclasses.dataclass
class PrefillRecord:
    """The atomic migration handoff unit :meth:`InferenceEngine.prefill`
    returns: the request's filled KV pages (block-table order, ONE
    refcount per page owned by this record) plus everything the decode
    side needs to continue the request token-identically.  Ownership is
    linear — exactly one of :meth:`InferenceEngine.release_prefill` or
    the KVMigrator's export seam consumes it."""

    prompt: list[int]
    max_new_tokens: int
    temperature: float
    seed: int
    eos_id: int | None
    pages: list[int]        # block-table order; record owns one ref each
    cached_len: int         # positions aliased from the prefill-side cache
    generation: int         # prefill-engine weight generation of the pages


class MigrationTicket:
    """Accept/reject signal for one :meth:`admit_from_pages` handoff.

    The serve thread resolves it at the drain fence — accepted means the
    engine now owns the pages and the request WILL decode (its
    completion arrives through the pending handle); rejected means the
    refcounts were already released and the caller should requeue."""

    def __init__(self):
        self._ev = threading.Event()
        self._accepted = False          # write-once before _ev.set()
        self._reason: str | None = None

    def _resolve(self, accepted: bool, reason: str | None = None) -> None:
        self._accepted = accepted
        self._reason = reason
        self._ev.set()

    def wait(self, timeout: float | None = None) -> bool:
        """True = admitted, False = rejected (see :attr:`reason`)."""
        if not self._ev.wait(timeout):
            raise TimeoutError("migration ticket unresolved — is the "
                               "decode engine's serve loop running?")
        return self._accepted

    @property
    def reason(self) -> str | None:
        return self._reason


@dataclasses.dataclass
class _MigratedIn:
    """One migrated request parked for the serve thread's drain fence.
    ``pages`` arrive already increfed on THIS engine's pool (claim +
    alloc happened in the KVMigrator); ownership passes to the engine
    the moment the record enters ``_migrated_in``."""

    pending: PendingResult
    pages: list[int]                  # block-table order, decode-side ids
    uploads: list                     # [(page_id, [{name: ndarray}, ...])]
    generation: int | None            # decode generation the claim assumed
    ticket: MigrationTicket


@dataclasses.dataclass
class _Slot:
    """Host-side record of one occupied cache row."""

    pending: PendingResult
    delivered: list = dataclasses.field(default_factory=list)
    admitted_s: float = 0.0
    first_token_s: float | None = None
    # weight generation this request was admitted (and will fully decode)
    # under — swaps apply only at fences with every slot free, so the
    # stamp is exact, not advisory
    generation: int = 0
    loaded_step: int | None = None


class InferenceEngine:
    """Continuous-batching decode over a trained ``TransformerLM``.

    ``params`` may be passed directly, or loaded from ``checkpoint`` (a
    directory path or a :class:`CheckpointManager`) — the engine opens
    checkpoint directories READ-ONLY and restores ``latest_valid_step()``.
    ``model.init`` shapes the restore template, so the checkpoint must
    match ``model.cfg``.
    """

    def __init__(self, model, params=None, checkpoint=None,
                 cfg: ServingConfig = ServingConfig(),
                 draft_model=None, draft_params=None):
        # with the persistent cache on, the warmup compiles hit disk on
        # a restart (policy and placement: parallel/compile_cache.py)
        setup_compile_cache()
        self.model = model
        self.cfg = cfg
        if cfg.prefix_cache and not cfg.paged:
            raise ValueError("prefix_cache requires paged=True (sharing is "
                             "block-table aliasing)")
        if cfg.role not in ("unified", "prefill", "decode"):
            raise ValueError(f"role must be unified/prefill/decode, "
                             f"got {cfg.role!r}")
        if cfg.role == "prefill" and not cfg.paged:
            raise ValueError("role='prefill' requires paged=True — the "
                             "migration unit is a KV page")
        if cfg.kv_quant is not None:
            if not cfg.paged:
                raise ValueError("kv_quant requires paged=True (scales live "
                                 "beside the page pool)")
            from ..ops.pallas import kv_quant as kvq
            kvq.storage_dtype(cfg.kv_quant)  # validates mode / fp8 support
        if cfg.speculative:
            if draft_model is None or draft_params is None:
                raise ValueError("speculative=True needs draft_model and "
                                 "draft_params (see zoo.draft_lm)")
            if (draft_model.cfg.vocab_size != model.cfg.vocab_size
                    or draft_model.cfg.max_len != model.cfg.max_len):
                raise ValueError("draft model must share the target's "
                                 "vocab_size and max_len")
            if cfg.spec_k < 1:
                raise ValueError("spec_k must be >= 1")
        self._draft_model = draft_model if cfg.speculative else None
        self._draft_params = draft_params if cfg.speculative else None
        # paged sizing: pages_per_slot covers max_len; one EXTRA physical
        # trash page (index num_pages) absorbs the masked writes of
        # inactive rows, whose stale block-table entries must never point
        # at reallocatable pages
        self._page_size = cfg.page_size
        self._pages_per_slot = -(-model.cfg.max_len // cfg.page_size)
        self._num_pages = (cfg.num_pages if cfg.num_pages is not None
                           else cfg.slots * self._pages_per_slot)
        self._pool = (PagePool(self._num_pages, cfg.page_size)
                      if cfg.paged else None)
        self._page_bytes = kv_page_bytes(model.cfg, cfg.page_size,
                                         cfg.kv_quant)
        # per-tier queue depth: the autoscaler distinguishes prefill
        # pressure (bursty, compute-bound) from decode pressure (steady,
        # memory-bound) by gauge name; unified keeps the classic name
        self._queue = RequestQueue(
            cfg.max_queue, cfg.max_batch_delay_ms,
            depth_gauge={"prefill": "serving.queue.depth.prefill",
                         "decode": "serving.queue.depth.decode"}.get(
                             cfg.role, "serving.queue.depth"))
        self._ckpt: CheckpointManager | None = None
        self._loaded_step: int | None = None
        if checkpoint is not None:
            self._ckpt = (checkpoint if isinstance(checkpoint, CheckpointManager)
                          else CheckpointManager.open_read_only(checkpoint))
        if params is None:
            if self._ckpt is None:
                raise ValueError("need params or a checkpoint to serve from")
            step = self._ckpt.latest_valid_step()
            if step is None:
                raise FileNotFoundError(
                    f"no verified checkpoint under {self._ckpt.directory}")
            template = model.init(jax.random.key(0))
            restored = self._ckpt.restore(template, step=step)
            params = restored["params"]
            self._loaded_step = restored["step"]
        # _lock guards the params swap AND the slot bookkeeping shared
        # between the serve thread and callers (stop/stats/HTTP handlers);
        # _state is deliberately OUTSIDE it — serve-thread-owned, see
        # warmup().  The guarded-by annotations are the LK01 contract:
        # every non-__init__ write must hold the lock.
        self._lock = threading.Lock()
        # _raw_params is the unquantized tree (also the reload restore
        # template — checkpoints never contain *_q leaves); _params is
        # what decode actually reads, int8-quantized when opted in
        self._raw_params = params                # guarded-by: self._lock
        self._params = self._maybe_quantize(params)  # guarded-by: self._lock
        # generation consistency (DESIGN.md §23): reload() STAGES the new
        # tree; the swap applies only at a fence with every slot free, so
        # every response decodes start-to-finish under ONE generation.
        # _generation counts applied swaps; _staged is the parked
        # (raw, quantized, step) tuple awaiting an all-slots-free fence.
        self._generation = 0                     # guarded-by: self._lock
        self._staged: tuple | None = None        # guarded-by: self._lock
        self._state = self._init_state()
        # device-resident chaos flags, built OUTSIDE the hot loop — the
        # decode segment must not upload scalars under hot_loop_guard
        self._garble = (jnp.int32(0), jnp.int32(1))
        # shardguard baseline mode: the first decode dispatch captures the
        # params/state placements; a later dispatch arriving differently
        # placed (e.g. a reload that device_puts onto the wrong sharding)
        # is counted as implicit resharding.  One flag check when off.
        self._step_fn = SHARDGUARD.wrap(
            "serving.decode_step",
            jax.jit(
                _named("decode_step", self._build_step()),
                donate_argnums=(2,) if cfg.speculative else (1,)))
        # brownout seam (DESIGN.md §26): a speculative engine also carries
        # the PLAIN step, compiled at warmup alongside the spec one, so
        # ladder level 1 (disable speculation) swaps dispatch at a fence
        # with no compile stall and no parity change — the draft only ever
        # decided how many tokens emit per dispatch, never which
        self._spec_enabled = cfg.speculative         # guarded-by: self._lock
        self._plain_step_fn = (SHARDGUARD.wrap(
            "serving.decode_step_plain",
            jax.jit(_named("decode_step", self._build_plain_step()),
                    donate_argnums=(1,)))
            if cfg.speculative else None)
        self._max_new_cap: int | None = None         # guarded-by: self._lock
        self._admission_hook = None                  # guarded-by: self._lock
        self._step_compiled = False
        self._warmed = False   # True once warmup() finished (healthz gate)
        self._admit_fns: dict[int, Callable] = {}    # guarded-by: self._lock
        self._slots: dict[int, _Slot] = {}           # guarded-by: self._lock
        self._slot_pages: dict[int, list[int]] = {}  # guarded-by: self._lock
        self._free: list[int] = list(range(cfg.slots))  # guarded-by: self._lock
        # pages quarantined by an off-thread clear_prefix (reload): the
        # serve thread wipes them at its next fence, then requeues them
        self._pending_wipe: list[int] = []           # guarded-by: self._lock
        # ---- disagg tier (DESIGN.md §27) ----
        # serializes prefill()/release_prefill()/read_pages(): on a
        # prefill-role engine (no serve thread) _state is owned by
        # whichever worker holds this lock
        self._prefill_lock = threading.Lock()
        # migrated requests parked for the serve thread's drain fence
        self._migrated_in: list[_MigratedIn] = []    # guarded-by: self._lock
        # lazily compiled draft-only prefill per bucket (speculative
        # decode engines rebuild the migrated request's draft cache row
        # locally — draft state never crosses the wire, and it only ever
        # decides accept LENGTH, never which tokens emit)
        self._draft_prefill_fns: dict[int, Callable] = {}  # guarded-by: self._lock
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._admitted = 0                           # guarded-by: self._lock
        self._completed = 0                          # guarded-by: self._lock
        # XLA cost of one decode dispatch (captured at warmup) — feeds the
        # live serving.decode_mfu gauge at every resolve fence
        self._decode_cost = None                     # serve-thread-owned

    def _maybe_quantize(self, params):
        """The serving tree decode reads: unchanged by default; with
        ``int8_decode`` the bandwidth-heavy matrices (FFN w1/w2, LM head)
        are replaced by int8 + per-channel-scale copies, and
        ``decode_step``/``_ffn`` pick the int8 path on key presence."""
        if not self.cfg.int8_decode:
            return params
        from ..ops.pallas.matmul_int8 import quantize_params_for_decode
        with allow_transfers(), METRICS.time("serving.quantize"):
            return quantize_params_for_decode(params, self.model.cfg)

    # ------------------------------------------------------------ device state
    def _init_state(self) -> dict:
        cfg = self.model.cfg
        S = self.cfg.slots
        state = {
            "toks": jnp.zeros((S, cfg.max_len), jnp.int32),
            "pos": jnp.zeros((S,), jnp.int32),
            "limit": jnp.zeros((S,), jnp.int32),
            "temp": jnp.zeros((S,), jnp.float32),
            "keys": jax.random.split(jax.random.key(0), S),
            "active": jnp.zeros((S,), bool),
        }
        if self.cfg.paged:
            # +1 physical page: the trash page every inactive block-table
            # row points at, so masked writes never land on real pages
            if self.cfg.kv_quant is not None:
                from ..ops.pallas.kv_quant import init_quantized_paged_cache
                state["pages"] = init_quantized_paged_cache(
                    cfg, self._num_pages + 1, self._page_size,
                    self.cfg.kv_quant)
            else:
                state["pages"] = init_paged_cache(
                    cfg, self._num_pages + 1, self._page_size)
            state["bt"] = jnp.full((S, self._pages_per_slot),
                                   self._num_pages, jnp.int32)
        else:
            state["cache"] = init_decode_cache(cfg, S)
        if self.cfg.speculative:
            state["draft_cache"] = init_decode_cache(self._draft_model.cfg, S)
        return state

    def _paged_attn_fn(self):
        """The paged-attention read the step uses: None selects the
        bitwise jnp gather path; any other name resolves a registry
        candidate — default off, unmeasured."""
        impl = self.cfg.paged_attention_impl
        if impl == "gather":
            return None
        from ..ops.pallas import registry as kernel_registry
        kind = ("paged_attention_int8" if self.cfg.kv_quant is not None
                else "paged_attention")
        return kernel_registry.get(kind, impl).fn

    def _build_step(self) -> Callable:
        if self.cfg.speculative:
            return self._build_spec_step()
        return self._build_plain_step()

    def _build_plain_step(self) -> Callable:
        cfg = self.model.cfg
        paged = self.cfg.paged
        attn_fn = self._paged_attn_fn() if paged else None

        def step(params, state):
            """Advance every occupied slot one token.

            Inactive / exhausted rows still flow through the batched
            matmuls (masked no-ops — cheaper than reshaping the batch),
            but their RNG keys, positions and token buffers are frozen
            and they emit -1.
            """
            toks, pos = state["toks"], state["pos"]
            temp, active, limit = state["temp"], state["active"], state["limit"]
            row = jnp.arange(toks.shape[0])
            cur = toks[row, pos]
            if paged:
                logits, pages = decode_step_paged(
                    params, state["pages"], state["bt"], cur, pos, cfg,
                    attn_fn=attn_fn)
                kv_update = {"pages": pages}
            else:
                logits, cache = decode_step(params, state["cache"], cur, pos,
                                            cfg)
                kv_update = {"cache": cache}
            # per-slot RNG, exactly Transformer.sample's kv stream: split
            # the slot key, carry the first half, draw from the second
            with jax.named_scope("sample"):
                pair = jax.vmap(jax.random.split)(state["keys"])  # (S, 2) keys
                carry, sub = pair[:, 0], pair[:, 1]
                safe_t = jnp.where(temp > 0, temp, 1.0)
                drawn = jax.vmap(jax.random.categorical)(
                    sub, logits / safe_t[:, None])
                pick = jnp.where(
                    temp > 0, drawn.astype(jnp.int32),
                    jnp.argmax(logits, axis=-1).astype(jnp.int32))
            can = active & (pos < limit) & (pos + 1 < cfg.max_len)
            emitted = jnp.where(can, pick, -1)
            new_pos = jnp.where(can, pos + 1, pos)
            toks = toks.at[row, new_pos].set(
                jnp.where(can, pick, toks[row, new_pos]))
            kd = jax.random.key_data(state["keys"])
            keys = jax.random.wrap_key_data(
                jnp.where(can[:, None], jax.random.key_data(carry), kd))
            new_state = dict(state, toks=toks, pos=new_pos, keys=keys,
                             **kv_update)
            return new_state, emitted

        return step

    def _build_spec_step(self) -> Callable:
        """Speculative decode dispatch: draft proposes ``spec_k`` greedy
        tokens, the target verifies the whole window at once, and up to
        ``spec_k + 1`` tokens emit.

        Parity argument (DESIGN.md §17): window logits ``L_0..L_k`` are
        the target's own next-token distributions at positions
        ``pos..pos+k`` (the windowed pass is bitwise W sequential steps).
        Token ``i`` is drawn from ``L_i`` with the request's i-th key
        split — the exact op the non-speculative step would run — and
        emits only while every earlier draft proposal matched its draw,
        i.e. while the sequence prefix equals what sequential decoding
        would have produced.  Keys advance by exactly the number of
        emitted tokens.  The draft therefore controls throughput
        (``serving.spec_accept_len``), never content."""
        cfg = self.model.cfg
        dcfg = self._draft_model.cfg
        paged = self.cfg.paged
        k_spec = self.cfg.spec_k
        W = k_spec + 1

        def step(params, dparams, state, garble):
            toks, pos = state["toks"], state["pos"]
            temp, active, limit = state["temp"], state["active"], state["limit"]
            S = toks.shape[0]
            row = jnp.arange(S)
            cur = toks[row, pos]
            # -- draft proposal chain (greedy; near max_len the clamped
            # draft-cache writes can degrade proposals — accept rate
            # drops, parity is untouched since only target draws emit)
            dcache = state["draft_cache"]
            proposals = []
            inp = cur
            for i in range(k_spec):
                d_logits, dcache = decode_step(dparams, dcache, inp, pos + i,
                                               dcfg)
                nxt = jnp.argmax(d_logits, axis=-1).astype(jnp.int32)
                # chaos serving.draft: a garbled draft must only shrink
                # accept length, never change emitted tokens
                nxt = (nxt + garble) % cfg.vocab_size
                proposals.append(nxt)
                inp = nxt
            d = jnp.stack(proposals, axis=1)                     # (S, k)
            window = jnp.concatenate([cur[:, None], d], axis=1)  # (S, W)
            # -- one windowed verify on the target
            if paged:
                logits, pages = decode_window_paged(
                    params, state["pages"], state["bt"], window, pos, cfg)
                kv_update = {"pages": pages}
            else:
                logits, cache = decode_window(params, state["cache"], window,
                                              pos, cfg)
                kv_update = {"cache": cache}
            # -- the offline key stream: split i times, draw pick_i from
            # L_i with sub_i; emitted count m selects carry_m below
            safe_t = jnp.where(temp > 0, temp, 1.0)
            key_stack = [jax.random.key_data(state["keys"])]     # carry_0
            picks = []
            kcur = state["keys"]
            for i in range(W):
                with jax.named_scope("sample"):
                    pair = jax.vmap(jax.random.split)(kcur)
                    kcur, sub = pair[:, 0], pair[:, 1]
                    drawn = jax.vmap(jax.random.categorical)(
                        sub, logits[:, i] / safe_t[:, None])
                    pick = jnp.where(
                        temp > 0, drawn.astype(jnp.int32),
                        jnp.argmax(logits[:, i], axis=-1).astype(jnp.int32))
                picks.append(pick)
                key_stack.append(jax.random.key_data(kcur))
            picks = jnp.stack(picks, axis=1)                     # (S, W)
            off = jnp.arange(W, dtype=jnp.int32)[None, :]
            can = (active[:, None] & (pos[:, None] + off < limit[:, None])
                   & (pos[:, None] + off + 1 < cfg.max_len))     # (S, W)
            match = jnp.concatenate(
                [jnp.ones((S, 1), bool), d == picks[:, :k_spec]], axis=1)
            emit = jnp.cumprod((can & match).astype(jnp.int32),
                               axis=1).astype(bool)              # (S, W)
            m = emit.sum(axis=1).astype(jnp.int32)               # (S,)
            emitted = jnp.where(emit, picks, -1)
            tpos = pos[:, None] + 1 + off                        # (S, W)
            flat = jnp.where(emit, row[:, None] * cfg.max_len + tpos,
                             S * cfg.max_len)
            toks = toks.reshape(-1).at[flat.reshape(-1)].set(
                picks.reshape(-1), mode="drop").reshape(S, cfg.max_len)
            kstack = jnp.stack(key_stack, axis=0)                # (W+1, S, ..)
            keys = jax.random.wrap_key_data(kstack[m, row])      # carry_m

            new_state = dict(state, toks=toks, pos=pos + m, keys=keys,
                             draft_cache=dcache, **kv_update)
            return new_state, emitted

        return step

    # ------------------------------------------------------------ prefill
    def _prompt_bucket(self, n: int) -> int:
        """Power-of-two prompt ladder (the PR-2 pad-batch discipline):
        one compiled prefill per bucket, so recompiles are bounded by
        ``log2(max_len)`` regardless of prompt-length diversity."""
        b = self.cfg.min_prefill_bucket
        while b < n:
            b <<= 1
        return min(b, self.model.cfg.max_len)

    def _admit_for(self, bucket: int) -> Callable:
        with self._lock:
            cached = self._admit_fns.get(bucket)
        if cached is not None:
            return cached
        cfg = self.model.cfg
        paged = self.cfg.paged
        spec = self.cfg.speculative
        dcfg = self._draft_model.cfg if spec else None
        ps = self._page_size
        n_slot_pages = self._pages_per_slot

        def admit(params, dparams, state, prompt, p_len, cached_len, slot,
                  key, temp, max_new):
            """Prefill ``prompt[:p_len]`` on a batch-of-1 cache through
            the SAME ``decode_step`` the steady loop uses (numerics cannot
            diverge from ``Transformer.sample``'s kv path), then scatter
            the row into the slot pool (dense) or the slot's pages.
            Masked iterations are no-ops: one executable per bucket.

            Paged: the batch-of-1 cache starts as a GATHER of the slot's
            block-table row, so positions ``< cached_len`` (aliased
            prefix pages) are already populated and the loop skips them;
            the scatter-back rewrites shared pages with bitwise-identical
            values (prefill is position-wise deterministic).  Speculative:
            the draft cache prefills alongside (always from 0 — the
            prefix cache holds target pages only)."""
            if paged:
                bt_row = lax.dynamic_slice(
                    state["bt"], (slot, jnp.int32(0)), (1, n_slot_pages))
                # quant-transparent: a quantized pool (kv_quant) gathers
                # DEQUANTIZED content, so the prefill loop below runs the
                # same float arithmetic either way
                cache1 = [dict(zip(("k", "v"), gather_paged_layer(
                    c, bt_row, cfg.max_len, cfg.dtype)))
                          for c in state["pages"]]
            else:
                cache1 = init_decode_cache(cfg, 1)
            dcache1 = init_decode_cache(dcfg, 1) if spec else jnp.int32(0)
            last = jnp.maximum(p_len - 2, 0)

            def body(i, carry):
                c, dc = carry
                ii = jnp.minimum(i, last)
                tok_i = lax.dynamic_slice(prompt, (ii,), (1,))
                _, c_new = decode_step(params, c, tok_i, ii, cfg)
                use = (i >= cached_len) & (i < p_len - 1)
                c = jax.tree_util.tree_map(
                    lambda a, b: jnp.where(use, a, b), c_new, c)
                if spec:
                    _, dc_new = decode_step(dparams, dc, tok_i, ii, dcfg)
                    dc = jax.tree_util.tree_map(
                        lambda a, b: jnp.where(i < p_len - 1, a, b),
                        dc_new, dc)
                return c, dc

            cache1, dcache1 = lax.fori_loop(0, bucket, body, (cache1, dcache1))
            if paged:
                t = jnp.arange(cfg.max_len, dtype=jnp.int32)[None, :]
                flat = paged_flat_index(bt_row, t, ps)[0]        # (max_len,)
                # quantize-at-write for kv_quant pools (scatter_paged_layer
                # requantizes only the row's pages; an aliased prefix page
                # rewrites with identical content → identical bytes)
                kv_update = {"pages": [
                    scatter_paged_layer(c, flat, c1["k"][0], c1["v"][0])
                    for c, c1 in zip(state["pages"], cache1)]}
            else:
                kv_update = {"cache": [
                    {"k": lax.dynamic_update_slice_in_dim(c["k"], c1["k"],
                                                          slot, axis=0),
                     "v": lax.dynamic_update_slice_in_dim(c["v"], c1["v"],
                                                          slot, axis=0)}
                    for c, c1 in zip(state["cache"], cache1)]}
            if spec:
                kv_update["draft_cache"] = [
                    {"k": lax.dynamic_update_slice_in_dim(c["k"], c1["k"],
                                                          slot, axis=0),
                     "v": lax.dynamic_update_slice_in_dim(c["v"], c1["v"],
                                                          slot, axis=0)}
                    for c, c1 in zip(state["draft_cache"], dcache1)]
            toks = lax.dynamic_update_slice(
                state["toks"], prompt[None, :], (slot, jnp.int32(0)))

            def put1(arr, v):
                return lax.dynamic_update_slice(
                    arr, jnp.reshape(v, (1,)).astype(arr.dtype), (slot,))

            kd = lax.dynamic_update_slice(
                jax.random.key_data(state["keys"]),
                jax.random.key_data(key)[None], (slot, jnp.int32(0)))
            return dict(
                state,
                toks=toks,
                # sample() prefills tokens 0..P-2; the first engine step
                # then processes token P-1 and draws the first new token
                pos=put1(state["pos"], p_len - 1),
                limit=put1(state["limit"], p_len - 1 + max_new),
                temp=put1(state["temp"], temp),
                active=put1(state["active"], True),
                keys=jax.random.wrap_key_data(kd),
                **kv_update,
            )

        prefill = jax.jit(_named(f"prefill_b{bucket}", admit),
                          donate_argnums=(2,))
        with self._lock:
            self._admit_fns[bucket] = prefill
        METRICS.increment("serving.prefill.recompile")
        return prefill

    # ------------------------------------------------------------ submission
    def submit(self, prompt, max_new_tokens: int, temperature: float = 0.0,
               seed: int = 0, eos_id: int | None = None,
               deadline_ms: float | None = None,
               tenant: str = "", priority: int = 0) -> PendingResult:
        """Validate + enqueue; returns a handle whose ``result()`` blocks.
        Raises ``ValueError`` on malformed requests (HTTP 400) and
        :class:`~.batcher.QueueFull` under backpressure (HTTP 429).
        ``tenant`` is an opaque caller identity for per-tenant accounting;
        it is folded ONCE here through the bounded label helper and the
        folded label rides the request — downstream metric sites never
        see the raw string (graftlint OB03).  ``priority`` > 0 marks
        BACKGROUND work: claimed only when no interactive request waits
        (aging prevents starvation) and shed first under brownout."""
        cfg = self.model.cfg
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("empty prompt")
        if any(not 0 <= t < cfg.vocab_size for t in prompt):
            raise ValueError(f"prompt token out of range [0, {cfg.vocab_size})")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if len(prompt) + max_new_tokens > cfg.max_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds max_len ({cfg.max_len})")
        with self._lock:
            cap = self._max_new_cap
            hook = self._admission_hook
        if cap is not None and max_new_tokens > cap:
            # brownout level 2: serve a SHORTER completion instead of
            # shedding — the served tokens are exactly the offline
            # sample's prefix under the clamped budget, so token parity
            # holds for everything that is served
            max_new_tokens = cap
            METRICS.increment("serving.max_new_clamped")
        req = GenerateRequest(
            prompt=prompt, max_new_tokens=int(max_new_tokens),
            temperature=float(temperature), seed=int(seed),
            eos_id=eos_id if eos_id is not None else self.cfg.default_eos_id,
            deadline_s=(time.monotonic() + deadline_ms / 1000.0
                        if deadline_ms else None),
            tenant=TENANTS.label(str(tenant)) if tenant else "",
            priority=1 if int(priority) > 0 else 0)
        if hook is not None:
            # admission-side overload gate (control/overload.py): raises
            # a ServingRejected subclass — throttle/shed IS the API (429)
            hook(req)
        if _obs_enabled():
            # trace identity for the whole request: adopt the caller's
            # context (HTTP traceparent installed via trace.bind, or an
            # enclosing span), else mint — one trace_id spans queue wait,
            # prefill, every decode segment, and emit
            ctx = trace.current_trace_context()
            if ctx is not None:
                req.trace_id, req.parent_span_id = ctx
            else:
                req.trace_id = trace.new_trace_id()
            req.root_span_id = trace.new_span_id()
        METRICS.increment("serving.requests")
        return self._queue.submit(req)

    def generate(self, prompt, max_new_tokens: int, timeout: float = 60.0,
                 **kw) -> Completion:
        """Blocking convenience wrapper over :meth:`submit`."""
        return self.submit(prompt, max_new_tokens, **kw).result(timeout)

    # ------------------------------------------------------------ serve loop
    def start(self, warmup: bool = True) -> "InferenceEngine":
        if self._thread is not None:
            return self
        if warmup:
            self.warmup()
        if self.cfg.role == "prefill":
            # prefill tier: no decode loop to run — work arrives through
            # prefill() on the scheduler's worker threads, and _state
            # stays owned by whoever holds _prefill_lock
            return self
        self._stop.clear()
        self._thread = threading.Thread(target=self._serve_loop, daemon=True,
                                        name="serving-engine")
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        self._queue.wake()   # kick the serve loop out of its idle wait
        if self._thread is not None:
            self._thread.join(timeout=30.0)
            self._thread = None
        with self._lock:
            dead = {s: self._slots.pop(s) for s in list(self._slots)}
            pages = {s: self._slot_pages.pop(s, [])
                     for s in list(self._slot_pages)}
            # start() after stop() is supported: restore the FULL slot
            # range — a dead slot's id must not leak out of the pool
            self._free = list(range(self.cfg.slots))
            pending, self._pending_wipe = self._pending_wipe, []
            migrated, self._migrated_in = self._migrated_in, []
        for sl in dead.values():
            sl.pending._fail(
                RuntimeError("engine stopped with request in flight"))
        for rec in migrated:
            # reject, never corrupt: the pages are decreffed below and
            # the scheduler requeues on MigrationRejected
            rec.ticket._resolve(False, "engine stopped")
            rec.pending._fail(MigrationRejected(
                "engine stopped before migrated request was admitted"))
        # the serve thread is joined, so _state is safe to touch here.
        # Reset the dead rows the way _evict would have — deactivate,
        # release K/V and (paged) park the block tables on the trash
        # page — so a restarted decode loop, which writes EVERY row's
        # K/V through its table, can never scribble on pages the pool
        # reallocates to new requests.
        if dead or pending or migrated:
            with allow_transfers():
                if self.cfg.paged:
                    freed = list(pending)
                    for pg in pages.values():
                        freed.extend(self._pool.decref(pg))
                    for rec in migrated:
                        freed.extend(self._pool.decref(rec.pages))
                    bt = self._state["bt"]
                    active = self._state["active"]
                    for s in dead:
                        bt = bt.at[s].set(self._num_pages)
                        active = active.at[s].set(False)
                    # graftlint: disable=LK01 — _state is serve-thread-
                    # owned; the join above is the happens-before edge
                    self._state = dict(self._state, bt=bt, active=active)
                    self._wipe_pages(freed)
                    if pending:
                        self._pool.requeue(pending)
                else:
                    mask = np.zeros((self.cfg.slots,), bool)
                    mask[list(dead)] = True
                    self._state = dict(
                        self._state,
                        cache=reset_cache_slots(self._state["cache"],
                                                jnp.asarray(mask)),
                        active=self._state["active"]
                        .at[jnp.asarray(list(dead), jnp.int32)].set(False))
        for p in self._queue.drain():
            p._fail(RuntimeError("engine stopped before request was admitted"))

    def __enter__(self) -> "InferenceEngine":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    def _bucket_ladder(self) -> list[int]:
        """Every prefill bucket traffic can ever hit: the power-of-two
        ladder from ``min_prefill_bucket`` up to (and including) the
        ``max_len`` cap bucket."""
        out = []
        b = self.cfg.min_prefill_bucket
        while b < self.model.cfg.max_len:
            out.append(b)
            b <<= 1
        out.append(self.model.cfg.max_len)
        return sorted(set(out))

    def warmup(self) -> None:
        """Compile the steady-state step and EVERY prefill bucket up to
        ``max_len`` before traffic (with the PR-2 persistent compile
        cache configured these are disk hits on restart) — first-request
        TTFT never pays a compile stall, whatever the prompt length, and
        ``serving.prefill.recompile`` stays at bucket-ladder count for
        the engine's whole lifetime."""
        with allow_transfers(), METRICS.time("serving.warmup"):
            pages: list[int] = []
            try:
                if self.cfg.paged:
                    # slot 0 needs a real block-table row for the dummy
                    # admits below; released (and re-trashed) in finally.
                    # A pool smaller than pages_per_slot is legal (sized
                    # for short requests): warm with what it has — the
                    # row's tail parks on the trash page, exactly like
                    # an admitted short request's
                    n_warm = min(self._pages_per_slot, self._num_pages)
                    pages = self._pool.alloc(n_warm)
                    row = pages + [self._num_pages] * (
                        self._pages_per_slot - n_warm)
                    # graftlint: disable=LK01 — _state is serve-thread-
                    # owned; warmup (and every other flagged site) runs
                    # either before Thread.start() or ON the serve loop,
                    # so there is a happens-before edge, never a race
                    self._state = dict(
                        self._state,
                        bt=self._state["bt"].at[0].set(
                            jnp.asarray(row, jnp.int32)))
                dparams = self._draft_params if self.cfg.speculative else {}
                # cost capture lowers with the concrete args BEFORE the
                # donating call (lowering reads avals only, never buffers)
                if self.cfg.role == "prefill":
                    # a prefill-role engine never runs the decode step —
                    # skipping its compile makes prefill-tier spin-up
                    # (and the autoscaler's scale-up path) proportionally
                    # cheaper; the bucket ladder below is the whole job
                    state = self._state
                elif self.cfg.speculative:
                    self._decode_cost = COSTS.capture(
                        "serving.decode_step", self._step_fn,
                        self._params, dparams, self._state, jnp.int32(0))
                    state, _ = self._step_fn(self._params, dparams,
                                             self._state, jnp.int32(0))
                    # the brownout fallback step compiles NOW, not at the
                    # moment the ladder disables speculation — degrading
                    # under load must never pay a compile stall
                    state, _ = self._plain_step_fn(self._params, state)
                else:
                    self._decode_cost = COSTS.capture(
                        "serving.decode_step", self._step_fn,
                        self._params, self._state)
                    state, _ = self._step_fn(self._params, self._state)
                self._step_compiled = True
                for bucket in self._bucket_ladder():
                    fn = self._admit_for(bucket)
                    state = fn(self._params, dparams, state,
                               jnp.zeros((bucket,), jnp.int32), jnp.int32(1),
                               jnp.int32(0), jnp.int32(0), jax.random.key(0),
                               jnp.float32(0.0), jnp.int32(0))
                # the warmup admits occupied slot 0 with a dummy —
                # deactivate, and park its block-table row back on the
                # trash page so the freed pages are writable by nobody.
                # graftlint: disable=LK01 — _state is serve-thread-owned
                # (every other write site runs on the serve loop); warmup
                # runs strictly before Thread.start(), which is a
                # happens-before edge, so this write can never race
                self._state = dict(
                    state, active=jnp.zeros_like(state["active"]))
            finally:
                if pages:
                    freed = self._pool.decref(pages)
                    self._wipe_pages(freed)
                    self._state = dict(
                        self._state,
                        bt=self._state["bt"].at[0].set(self._num_pages))
        # the warmed flag flips only after the step fn(s) AND the full
        # prefill bucket ladder compiled — the signal the router's
        # scale-up path gates ring admission on (a cold replica on the
        # ring is a compile-storm TTFT spike for the keys it inherits)
        self._warmed = True

    def _wipe_pages(self, freed: list[int]) -> None:
        """Zero physical pages whose refcount just hit zero (never an
        aliased page — ``PagePool.decref`` only returns dead ones)."""
        if not freed or not self.cfg.paged:
            return
        with trace.span("serving.wipe", pages=len(freed)):
            mask = np.zeros((self._num_pages + 1,), bool)
            mask[freed] = True
            self._state = dict(
                self._state,
                pages=reset_cache_pages(self._state["pages"],
                                        jnp.asarray(mask)))

    def _serve_loop(self) -> None:
        while not self._stop.is_set():
            try:
                self._serve_once()
            except Exception as e:  # defensive: a wedged loop strands callers
                METRICS.increment("serving.engine.errors")
                with self._lock:
                    dead = [self._slots.pop(s) for s in list(self._slots)]
                    self._slot_pages.clear()
                    self._free = list(range(self.cfg.slots))
                    # pool.reset() below rebuilds the free list wholesale,
                    # so quarantined page ids would go stale — drop them
                    self._pending_wipe.clear()
                    migrated, self._migrated_in = self._migrated_in, []
                for sl in dead:
                    sl.pending._fail(e)
                for rec in migrated:
                    # pool.reset() reclaims their pages wholesale below
                    rec.ticket._resolve(False, "serve loop crashed")
                    rec.pending._fail(MigrationRejected(
                        "serve loop crashed before admission"))
                if self._pool is not None:
                    self._pool.reset()
                with allow_transfers():
                    self._state = self._init_state()

    def _drain_pending_wipe(self) -> None:
        """Serve-thread half of reload's prefix invalidation: zero the
        pages :meth:`PagePool.clear_prefix` quarantined and only THEN
        hand them back to the free list.  Wipe-before-reallocatable —
        the pages are not allocatable until ``requeue``, so they can
        never be zeroed under a request that just acquired them; and
        the wipe itself runs HERE because ``_state`` is serve-thread-
        owned (reload must not touch it)."""
        with self._lock:
            pending, self._pending_wipe = self._pending_wipe, []
        if not pending:
            return
        with allow_transfers():
            self._wipe_pages(pending)
        self._pool.requeue(pending)

    def _serve_once(self) -> None:
        self._drain_pending_wipe()
        with self._lock:
            applied = self._try_apply_staged_locked()
            staged = self._staged is not None
        if applied:
            self._publish_generation_gauges()
        if not staged:
            # migrated requests enter the continuous batch HERE, between
            # decode segments — the admit_from_pages seam (DESIGN.md §27)
            self._drain_migrated()
        idle = not self._slots
        n_free = len(self._free)
        if n_free and not staged:
            # admission pauses while a swap is staged: in-flight slots
            # drain (each bounds its own decode budget), the fence
            # arrives, and queued requests then decode wholly under the
            # NEW generation — never a mid-request mix
            batch = self._queue.take(
                n_free, block_s=self.cfg.idle_wait_s if idle else 0.0)
            if batch:
                # admission is a deliberate host<->device seam (prompt
                # upload, request bookkeeping) — annotated, off the
                # per-token path
                with allow_transfers(), trace.span("serving.admit"):
                    self._admit(batch)
        if not self._slots:
            return
        METRICS.observe_time("serving.batch_fill_ratio",
                             len(self._slots) / self.cfg.slots,
                             buckets=FILL_BUCKETS)
        t0 = time.perf_counter()
        with hot_loop_guard(), trace.span("serving.decode_segment"):
            pending = self._decode_segment()
        with allow_transfers(), trace.span("serving.resolve"):
            self._resolve(pending, t0)

    def _admit(self, batch: list[PendingResult]) -> None:
        for p in batch:
            # atomic expiry-vs-admission: a deadline that passed between
            # the queue pop and this point 504s HERE, under the queue
            # lock, instead of occupying a slot to decode tokens nobody
            # is waiting for
            if not self._queue.claim(p):
                continue
            req: GenerateRequest = p.request
            if req.trace_id:
                t_claim = time.perf_counter()
                trace.record_span(
                    "serving.queue_wait", req.submitted_perf,
                    t_claim - req.submitted_perf, trace_id=req.trace_id,
                    parent_id=req.root_span_id, request=req.id)
            with self._lock:
                slot = self._free.pop()
                params = self._params
                # generation stamp is atomic with the params capture —
                # the pair can never disagree (DESIGN.md §23)
                gen, lstep = self._generation, self._loaded_step
            acquired: list[int] = []
            try:
                cached_len = 0
                if self.cfg.paged:
                    if FAULTS.check("serving.page_pool") is not None:
                        raise PagePoolExhausted(
                            "injected page-pool exhaustion (chaos site "
                            "serving.page_pool)")
                    usable = len(req.prompt) - 1
                    if self.cfg.prefix_cache:
                        # the lookup is atomic with a params re-capture:
                        # reload() swaps params AND clears the cache
                        # under this same lock, so every entry seen here
                        # holds K/V computed under exactly `params` — an
                        # aliased prefix can never mix weights with the
                        # prefill that extends it
                        with self._lock:
                            params = self._params
                            gen, lstep = self._generation, self._loaded_step
                            shared, cached_len = self._pool.lookup_prefix(
                                req.prompt, usable)
                        acquired.extend(shared)
                    # allocate for what THIS request can touch (prompt +
                    # budget, the engine writes positions [0, limit]),
                    # not max_len — the paged footprint win; the row's
                    # unneeded tail parks on the trash page, which decode
                    # may scribble on but never attends
                    need = -(-(len(req.prompt) + req.max_new_tokens)
                             // self._page_size)
                    acquired.extend(self._pool.alloc(need - len(acquired)))
                    row = acquired + [self._num_pages] * (
                        self._pages_per_slot - len(acquired))
                    self._state = dict(
                        self._state,
                        bt=self._state["bt"].at[slot].set(
                            jnp.asarray(row, jnp.int32)))
                bucket = self._prompt_bucket(len(req.prompt))
                prompt = np.zeros((bucket,), np.int32)
                prompt[:len(req.prompt)] = req.prompt
                admit_fn = self._admit_for(bucket)
                dparams = self._draft_params if self.cfg.speculative else {}
                args = (params, dparams, self._state, jnp.asarray(prompt),
                        jnp.int32(len(req.prompt)), jnp.int32(cached_len),
                        jnp.int32(slot), jax.random.key(req.seed),
                        jnp.float32(req.temperature),
                        jnp.int32(req.max_new_tokens))
                if _obs_enabled():
                    # per-bucket prefill cost (signature-cached: lowers
                    # once per bucket shape, then a dict hit per admit)
                    COSTS.capture(f"serving.prefill.b{bucket}", admit_fn,
                                  *args)
                t_pre = time.perf_counter()
                with trace.span("serving.prefill_dispatch", bucket=bucket):
                    self._state = admit_fn(*args)
                if req.trace_id:
                    trace.record_span(
                        "serving.prefill", t_pre,
                        time.perf_counter() - t_pre, trace_id=req.trace_id,
                        parent_id=req.root_span_id, request=req.id,
                        bucket=bucket)
                if self.cfg.prefix_cache:
                    # publish every full-page chain of this prompt —
                    # entries pin their pages with their own refcount.
                    # Skipped when a reload swapped params mid-prefill:
                    # these pages hold OLD-weight K/V the just-cleared
                    # cache must not re-learn
                    with self._lock:
                        if self._params is params:
                            self._pool.insert_prefix(req.prompt, acquired,
                                                     usable)
                    if cached_len:
                        METRICS.increment("serving.prefix_hits")
            except Exception as e:
                # fail only THIS request — the slot (and any pages it
                # acquired) go back to the pool; the rest of the batch
                # still admits.  PagePoolExhausted lands here too: 429
                # backpressure, not an engine error
                if acquired:
                    self._wipe_pages(self._pool.decref(acquired))
                if self.cfg.paged:
                    # park the row on the trash page again — a stale
                    # table must never alias reallocatable pages
                    self._state = dict(
                        self._state,
                        bt=self._state["bt"].at[slot].set(self._num_pages))
                with self._lock:
                    self._free.append(slot)
                if isinstance(e, PagePoolExhausted):
                    METRICS.increment("serving.page_pool_exhausted")
                    FLIGHTREC.note_429()
                else:
                    METRICS.increment("serving.engine.errors")
                p._fail(e)
                continue
            with self._lock:
                self._slots[slot] = _Slot(pending=p,
                                          admitted_s=time.monotonic(),
                                          generation=gen, loaded_step=lstep)
                self._slot_pages[slot] = acquired
                self._admitted += 1
            METRICS.increment("serving.admitted")
            self._publish_kv_gauges()

    # ------------------------------------------- disagg tier (DESIGN.md §27)
    @property
    def page_pool(self) -> PagePool | None:
        """The host-side page pool (None on dense engines).  The decode
        half of a migration claims and allocates against it — but only
        through the KVMigrator's export/import seams (graftlint DG01)."""
        return self._pool

    def prefill(self, prompt, max_new_tokens: int, temperature: float = 0.0,
                seed: int = 0, eos_id: int | None = None) -> PrefillRecord:
        """Prefill-ONLY admission (the prefill tier's entire job): fill
        the request's KV pages through the SAME compiled admit path a
        colocated request uses — numerics cannot diverge — then release
        the slot without decoding a single token.  Returns a
        :class:`PrefillRecord` owning one refcount per page: the atomic
        handoff unit the KVMigrator exports to a decode engine.

        Requires a paged engine with NO serve thread running (a
        ``role='prefill'`` engine never starts one): ``_state`` is owned
        by whichever worker holds ``_prefill_lock``.
        """
        if not self.cfg.paged:
            raise ValueError("prefill-only requires paged=True — the "
                             "migration unit is a KV page")
        if self._thread is not None:
            raise RuntimeError("prefill() needs exclusive ownership of the "
                               "device state — stop the serve loop first "
                               "(role='prefill' engines never start one)")
        cfg = self.model.cfg
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("empty prompt")
        if any(not 0 <= t < cfg.vocab_size for t in prompt):
            raise ValueError(f"prompt token out of range [0, {cfg.vocab_size})")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if len(prompt) + max_new_tokens > cfg.max_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds max_len ({cfg.max_len})")
        with self._prefill_lock, allow_transfers(), \
                METRICS.time("serving.prefill_only"):
            with self._lock:
                # a prefill-role engine has no serve loop to reach the
                # all-slots-free fence — prefill entry IS that fence
                applied = self._try_apply_staged_locked()
            if applied:
                self._publish_generation_gauges()
            self._drain_pending_wipe()
            with self._lock:
                if not self._free:
                    raise QueueFull("no free slot for prefill")
                slot = self._free.pop()
                params = self._params
                gen = self._generation
            acquired: list[int] = []
            try:
                cached_len = 0
                usable = len(prompt) - 1
                if self.cfg.prefix_cache:
                    # atomic with a params/generation re-capture, exactly
                    # like _admit: an aliased prefix can never mix
                    # weights with the prefill that extends it
                    with self._lock:
                        params = self._params
                        gen = self._generation
                        shared, cached_len = self._pool.lookup_prefix(
                            prompt, usable)
                    acquired.extend(shared)
                need = -(-(len(prompt) + max_new_tokens) // self._page_size)
                acquired.extend(self._pool.alloc(need - len(acquired)))
                row = acquired + [self._num_pages] * (
                    self._pages_per_slot - len(acquired))
                # graftlint: disable=LK01 — _state is prefill-lock-owned:
                # role='prefill' engines never start a serve thread, and
                # _prefill_lock serializes every prefill worker
                self._state = dict(
                    self._state,
                    bt=self._state["bt"].at[slot].set(
                        jnp.asarray(row, jnp.int32)))
                bucket = self._prompt_bucket(len(prompt))
                padded = np.zeros((bucket,), np.int32)
                padded[:len(prompt)] = prompt
                admit_fn = self._admit_for(bucket)
                dparams = self._draft_params if self.cfg.speculative else {}
                with trace.span("serving.prefill_dispatch", bucket=bucket):
                    self._state = admit_fn(
                        params, dparams, self._state, jnp.asarray(padded),
                        jnp.int32(len(prompt)), jnp.int32(cached_len),
                        jnp.int32(slot), jax.random.key(int(seed)),
                        jnp.float32(temperature), jnp.int32(max_new_tokens))
                if self.cfg.prefix_cache:
                    with self._lock:
                        if self._params is params:
                            self._pool.insert_prefix(prompt, acquired,
                                                     usable)
                    if cached_len:
                        METRICS.increment("serving.prefix_hits")
            except Exception:
                if acquired:
                    self._wipe_pages(self._pool.decref(acquired))
                self._state = dict(
                    self._state,
                    bt=self._state["bt"].at[slot].set(self._num_pages))
                with self._lock:
                    self._free.append(slot)
                raise
            # release the slot WITHOUT decoding: deactivate the row and
            # park its block table back on the trash page.  The pages
            # stay pinned by the record's refcounts — that handoff (not
            # the slot) is what migrates
            self._state = dict(
                self._state,
                active=self._state["active"].at[slot].set(False),
                bt=self._state["bt"].at[slot].set(self._num_pages))
            with self._lock:
                self._free.append(slot)
            METRICS.increment("serving.prefills")
            return PrefillRecord(
                prompt=prompt, max_new_tokens=int(max_new_tokens),
                temperature=float(temperature), seed=int(seed),
                eos_id=(eos_id if eos_id is not None
                        else self.cfg.default_eos_id),
                pages=acquired, cached_len=cached_len, generation=gen)

    def release_prefill(self, record: PrefillRecord) -> None:
        """Consume a :class:`PrefillRecord` without migrating it (abort
        path, chaos-killed worker): decref its pages and wipe the ones
        that died.  Safe only where :meth:`prefill` is safe — no serve
        thread owns ``_state``."""
        if not record.pages:
            return
        with self._prefill_lock, allow_transfers():
            pages, record.pages = record.pages, []
            self._wipe_pages(self._pool.decref(pages))

    def read_pages(self, ids) -> list[dict]:
        """Host copies of the given physical pages: one dict per layer
        mapping the pool's array names (``k``/``v``, plus
        ``k_scale``/``v_scale`` under kv_quant) to an ``(n, ...)``
        ndarray — a migration export's byte payload.  int8/GQA layouts
        ride through verbatim: whatever the pool stores is what moves,
        so the decode-side scatter is byte-identical."""
        if not self.cfg.paged:
            raise ValueError("read_pages needs a paged engine")
        with self._prefill_lock, allow_transfers():
            idx = jnp.asarray(list(ids), jnp.int32)
            return [{name: np.asarray(arr[idx])
                     for name, arr in layer.items()}
                    for layer in self._state["pages"]]

    def queue_wipe(self, pages: list[int]) -> None:
        """Hand quarantined pages (refcount already zero, off the free
        list) to the serve thread for zeroing — the migration-abort
        release: the KVMigrator cannot touch device state it does not
        own, and a page must never become allocatable before the serve
        thread wipes it (wipe-before-reallocatable, DESIGN.md §17)."""
        if not pages:
            return
        with self._lock:
            self._pending_wipe.extend(pages)
        self._queue.wake()

    def admit_from_pages(self, pending: PendingResult, *, pages: list[int],
                         uploads: list,
                         generation: int | None = None) -> MigrationTicket:
        """Queue a migrated request for admission into the continuous
        batch — the serve thread installs it between decode segments
        (:meth:`_drain_migrated`), so a migration never stalls in-flight
        decode slots.

        ``pages`` (block-table order) must already hold one refcount
        each on THIS engine's pool — the KVMigrator's hash-only claims
        plus its fresh allocations.  Ownership transfers to the engine
        atomically with the queue append: whatever happens next (admit,
        generation-mismatch reject, stop, crash) the engine releases
        them exactly once.  ``uploads`` carries device bytes only for
        pages that were actually moved; deduped pages are already
        resident.  Returns a :class:`MigrationTicket` resolved at the
        drain fence."""
        if not self.cfg.paged:
            raise ValueError("admit_from_pages needs a paged engine")
        if self.cfg.role == "prefill":
            raise ValueError("a prefill-role engine cannot decode")
        req: GenerateRequest = pending.request
        need = -(-(len(req.prompt) + req.max_new_tokens) // self._page_size)
        if len(pages) != need or need > self._pages_per_slot:
            raise ValueError(
                f"page count {len(pages)} does not cover prompt+budget "
                f"(need {need}, pages_per_slot {self._pages_per_slot})")
        ticket = MigrationTicket()
        with self._lock:
            self._migrated_in.append(_MigratedIn(
                pending=pending, pages=list(pages), uploads=list(uploads),
                generation=generation, ticket=ticket))
        self._queue.wake()   # break the serve loop's idle wait
        return ticket

    def _drain_migrated(self) -> None:
        """Serve-thread drain of :meth:`admit_from_pages` records: one
        free slot per record, between decode segments.  A record whose
        claim generation no longer matches (a reload applied since the
        KVMigrator planned the transfer) is REJECTED — pages released,
        ticket failed — because its deduped pages hold old-generation
        K/V; the scheduler requeues and re-migrates under the new
        weights.  Reject, never corrupt."""
        while True:
            with self._lock:
                if not self._migrated_in or not self._free \
                        or self._staged is not None:
                    return
                rec = self._migrated_in.pop(0)
                slot = self._free.pop()
                gen, lstep = self._generation, self._loaded_step
            with allow_transfers(), trace.span("serving.admit_migrated"):
                ok = (rec.generation is None or rec.generation == gen) \
                    and not rec.pending.done()
                if not ok:
                    # REJECT, do not fail: the pending handle stays open
                    # so the migrator can re-plan under the new weights
                    # and hand the same request back — the caller only
                    # ever sees a completion or a terminal failure
                    self._wipe_pages(self._pool.decref(rec.pages))
                    with self._lock:
                        self._free.append(slot)
                    rec.ticket._resolve(
                        False, "request done" if rec.pending.done()
                        else "weight generation moved since migration plan")
                    continue
                try:
                    self._admit_migrated(rec, slot)
                except Exception as e:
                    self._wipe_pages(self._pool.decref(rec.pages))
                    self._state = dict(
                        self._state,
                        bt=self._state["bt"].at[slot].set(self._num_pages),
                        active=self._state["active"].at[slot].set(False))
                    with self._lock:
                        self._free.append(slot)
                    rec.ticket._resolve(False, str(e))
                    rec.pending._fail(e)
                    METRICS.increment("serving.engine.errors")
                    continue
                with self._lock:
                    self._slots[slot] = _Slot(
                        pending=rec.pending, admitted_s=time.monotonic(),
                        generation=gen, loaded_step=lstep)
                    self._slot_pages[slot] = rec.pages
                    self._admitted += 1
                rec.ticket._resolve(True)
                METRICS.increment("serving.admitted")
                self._publish_kv_gauges()

    def _admit_migrated(self, rec: _MigratedIn, slot: int) -> None:
        """Install a migrated request into ``slot``: upload the moved
        page bytes (deduped pages are already resident — that is the
        point), point the block-table row at the pages, and write the
        same host-side admission state the compiled admit fn would have
        produced — WITHOUT re-running prefill FLOPs (the jitted admit
        recomputes the whole prompt; skipping that is migration's win).
        The RNG key is seeded exactly as colocated admission seeds it,
        so the decode draw stream is token-identical.  Speculative
        engines additionally rebuild the slot's draft cache with a
        draft-only prefill: draft-sized cost, parity-neutral (the draft
        only ever decides accept length, never which tokens emit)."""
        cfg = self.model.cfg
        req: GenerateRequest = rec.pending.request
        p_len = len(req.prompt)
        st = self._state
        if rec.uploads:
            ids = jnp.asarray([pid for pid, _ in rec.uploads], jnp.int32)
            new_pages = []
            for li, layer in enumerate(st["pages"]):
                upd = {}
                for name, arr in layer.items():
                    vals = np.stack([u[1][li][name] for u in rec.uploads])
                    upd[name] = arr.at[ids].set(
                        jnp.asarray(vals).astype(arr.dtype))
                new_pages.append(upd)
            st = dict(st, pages=new_pages)
        row = rec.pages + [self._num_pages] * (
            self._pages_per_slot - len(rec.pages))
        padded = np.zeros((cfg.max_len,), np.int32)
        padded[:p_len] = req.prompt
        kd = jax.random.key_data(st["keys"]).at[slot].set(
            jax.random.key_data(jax.random.key(req.seed)))
        self._state = dict(
            st,
            bt=st["bt"].at[slot].set(jnp.asarray(row, jnp.int32)),
            toks=st["toks"].at[slot].set(jnp.asarray(padded)),
            # identical to compiled admission: prefill covered positions
            # [0, p_len-1); the first decode step consumes token p_len-1
            pos=st["pos"].at[slot].set(p_len - 1),
            limit=st["limit"].at[slot].set(p_len - 1 + req.max_new_tokens),
            temp=st["temp"].at[slot].set(float(req.temperature)),
            keys=jax.random.wrap_key_data(kd),
            active=st["active"].at[slot].set(True))
        if self.cfg.speculative:
            bucket = self._prompt_bucket(p_len)
            pad_b = np.zeros((bucket,), np.int32)
            pad_b[:p_len] = req.prompt
            draft_fn = self._draft_prefill_for(bucket)
            self._state = dict(
                self._state,
                draft_cache=draft_fn(self._draft_params,
                                     self._state["draft_cache"],
                                     jnp.asarray(pad_b), jnp.int32(p_len),
                                     jnp.int32(slot)))
        if self.cfg.prefix_cache:
            # publish the migrated prompt's chains on the DECODE pool —
            # the next migration of this prefix is a hash-only claim.
            # Generation already matched at the drain fence, and a swap
            # cannot apply while this slot is out of _free
            with self._lock:
                self._pool.insert_prefix(req.prompt, rec.pages, p_len - 1)

    def _draft_prefill_for(self, bucket: int) -> Callable:
        """Draft-ONLY prefill for one bucket (speculative migrated
        admission): the target pages arrived by migration, but the draft
        cache is local state — rebuild just it, at draft-model cost."""
        with self._lock:
            cached = self._draft_prefill_fns.get(bucket)
        if cached is not None:
            return cached
        dcfg = self._draft_model.cfg

        def draft_admit(dparams, dcache, prompt, p_len, slot):
            dc1 = init_decode_cache(dcfg, 1)
            last = jnp.maximum(p_len - 2, 0)

            def body(i, dc):
                ii = jnp.minimum(i, last)
                tok_i = lax.dynamic_slice(prompt, (ii,), (1,))
                _, dc_new = decode_step(dparams, dc, tok_i, ii, dcfg)
                return jax.tree_util.tree_map(
                    lambda a, b: jnp.where(i < p_len - 1, a, b), dc_new, dc)

            dc1 = lax.fori_loop(0, bucket, body, dc1)
            return [
                {"k": lax.dynamic_update_slice_in_dim(c["k"], c1["k"],
                                                      slot, axis=0),
                 "v": lax.dynamic_update_slice_in_dim(c["v"], c1["v"],
                                                      slot, axis=0)}
                for c, c1 in zip(dcache, dc1)]

        draft_fn = jax.jit(draft_admit, donate_argnums=(1,))
        with self._lock:
            self._draft_prefill_fns[bucket] = draft_fn
        return draft_fn

    def _publish_kv_gauges(self) -> None:
        """Device-KV footprint gauges at admission/eviction fences: pages
        in use (shared pages count ONCE — that is the point), bytes, and
        bytes per occupied slot vs the dense ``S*max_len`` baseline."""
        from ..ops.pallas.kv_quant import kv_itemsize
        mcfg = self.model.cfg
        bits = kv_itemsize(self.cfg.kv_quant, mcfg.dtype) * 8
        METRICS.gauge("serving.kv_quant_bits", bits)
        if self._pool is None:
            dense = (mcfg.max_len * mcfg.kv_heads * mcfg.head_dim * 2
                     * mcfg.n_layers * jnp.dtype(mcfg.dtype).itemsize)
            METRICS.gauge("serving.kv_bytes", dense * self.cfg.slots)
            METRICS.gauge("serving.kv_bytes_per_slot", dense)
            return
        in_use = self._pool.in_use()
        with self._lock:
            occupied = len(self._slots)
            slot_pages: set[int] = set()
            for pages in self._slot_pages.values():
                slot_pages.update(pages)
        METRICS.gauge("serving.kv_pages_in_use", in_use)
        METRICS.gauge("serving.kv_pages_total", self._num_pages)
        METRICS.gauge("serving.kv_page_bytes", self._page_bytes)
        METRICS.gauge("serving.prefix_hit_rate", self._pool.hit_rate())
        METRICS.gauge("serving.kv_bytes", in_use * self._page_bytes)
        # per-slot cost counts pages *referenced by occupied slots* once
        # (shared prefix pages amortize — that is the point); cache pins
        # with no live reader are capacity (kv_bytes), not per-slot cost
        METRICS.gauge("serving.kv_bytes_per_slot",
                      len(slot_pages) * self._page_bytes / occupied
                      if occupied else 0.0)

    def _decode_segment(self) -> list:
        """Dispatch ``resolve_every`` decode steps with NO host syncs —
        the emitted-token arrays stay on device until ``_resolve``."""
        out = []
        with self._lock:
            params = self._params
            spec_on = self._spec_enabled
        # brownout level 1 applies HERE, at segment granularity: every
        # dispatch in a segment runs one path, and the swap happens at a
        # fence — in-flight slots keep exact token parity either way
        spec = self.cfg.speculative and spec_on
        step_fn = self._step_fn if spec or not self.cfg.speculative \
            else self._plain_step_fn
        dparams = self._draft_params if spec else None
        for _ in range(self.cfg.resolve_every):
            if FAULTS.check("serving.decode") is not None:
                # transient decode fault (chaos): this dispatch is skipped,
                # state is untouched, the next round retries — completions
                # stay token-identical under injection
                METRICS.increment("serving.decode.faults")
                continue
            if spec:
                # chaos serving.draft: garble every draft proposal this
                # dispatch — the traced flag shifts the draft argmax, so
                # accept length collapses but emitted tokens (drawn from
                # target logits) are untouched
                garbled = FAULTS.check("serving.draft") is not None
                if garbled:
                    METRICS.increment("serving.draft.faults")
                self._state, emitted = step_fn(
                    params, dparams, self._state,
                    self._garble[1 if garbled else 0])
            else:
                self._state, emitted = step_fn(params, self._state)
            out.append(emitted)
        METRICS.increment("serving.decode.dispatches", len(out))
        return out

    def _resolve(self, pending: list, t0: float) -> None:
        """The per-segment fence: ONE host pull for the whole segment's
        emitted tokens, then EOS/length bookkeeping and metrics."""
        if not pending:
            return
        em = np.asarray(jax.device_get(jnp.stack(pending)))  # (k, S[, W])
        if em.ndim == 2:
            em = em[:, :, None]   # non-speculative: window of one
        now = time.monotonic()
        seg_s = time.perf_counter() - t0
        n_steps = len(pending)
        METRICS.observe_many("serving.decode_step", [seg_s / n_steps] * n_steps)
        if self._decode_cost is not None and n_steps:
            # live utilization from the same cost_analysis() accounting
            # bench reports: flops of one dispatch / measured per-step time
            COSTS.publish_utilization(
                self._decode_cost, seg_s / n_steps,
                "serving.decode_mfu", "serving.decode_mbu")
        if self.cfg.speculative:
            # accepted-prefix length per dispatch per live slot (clipped
            # emissions at the limit count too — still useful signal)
            counts = (em >= 0).sum(axis=2)
            METRICS.observe_many(
                "serving.spec_accept_len",
                [float(c) for c in counts[counts > 0]],
                buckets=tuple(float(i)
                              for i in range(1, self.cfg.spec_k + 2)))
        delivered = 0
        for s in list(self._slots):
            sl = self._slots[s]
            req: GenerateRequest = sl.pending.request
            if req.trace_id:
                # one span per live slot per segment: all slots share the
                # wall-clock segment (they decode in the same dispatches)
                trace.record_span(
                    "serving.decode.segment", t0, seg_s,
                    trace_id=req.trace_id, parent_id=req.root_span_id,
                    request=req.id, slot=s, steps=n_steps)
            finish = None
            for t in em[:, s].reshape(-1):
                t = int(t)
                if t < 0:
                    continue
                delivered += 1
                if sl.first_token_s is None:
                    sl.first_token_s = now  # fence granularity, documented
                    METRICS.observe_time("serving.ttft",
                                         now - req.submitted_s)
                sl.delivered.append(t)
                if req.eos_id is not None and t == req.eos_id:
                    finish = "eos"
                    break
                if len(sl.delivered) >= req.max_new_tokens:
                    finish = "length"
                    break
            if finish is not None:
                self._evict(s, finish, now)
        if delivered:
            METRICS.increment("serving.tokens", delivered)
            if seg_s > 0:
                METRICS.gauge("serving.tokens_per_sec", delivered / seg_s)

    def _evict(self, s: int, finish: str, now: float) -> None:
        """Free slot ``s``: complete the caller, drop the host record,
        deactivate the row and release its K/V.  Dense: wipe the cache
        row.  Paged: decref the slot's pages — only pages whose refcount
        hits zero are wiped (an aliased prefix page stays live and
        intact for its other readers), and the block-table row parks on
        the trash page."""
        t_ev = time.perf_counter()
        with self._lock:
            sl = self._slots.pop(s)
            pages = self._slot_pages.pop(s, [])
            self._free.append(s)
            self._completed += 1
        # the freed row is reusable before these updates land only by
        # _admit, which runs on this same serve thread — no interleave
        if self.cfg.paged:
            self._state = dict(
                self._state,
                bt=self._state["bt"].at[s].set(self._num_pages),
                active=self._state["active"].at[s].set(False))
            self._wipe_pages(self._pool.decref(pages))
            self._publish_kv_gauges()
        else:
            mask = np.zeros((self.cfg.slots,), bool)
            mask[s] = True
            self._state = dict(
                self._state,
                cache=reset_cache_slots(self._state["cache"],
                                        jnp.asarray(mask)),
                active=self._state["active"].at[s].set(False))
        req = sl.pending.request
        METRICS.increment("serving.completed")
        METRICS.observe_time("serving.request_latency", now - req.submitted_s)
        if req.tenant:
            TENANTS.account("prompt_tokens", req.tenant, len(req.prompt))
            TENANTS.account("generated_tokens", req.tenant,
                            len(sl.delivered))
        sl.pending._complete(Completion(
            tokens=list(sl.delivered), finish_reason=finish,
            latency_s=now - req.submitted_s,
            ttft_s=(sl.first_token_s - req.submitted_s
                    if sl.first_token_s is not None else None),
            generation=sl.generation, loaded_step=sl.loaded_step))
        if req.trace_id:
            t_done = time.perf_counter()
            trace.record_span(
                "serving.emit", t_ev, t_done - t_ev, trace_id=req.trace_id,
                parent_id=req.root_span_id, request=req.id, finish=finish)
            # the request's root span: submit -> completion, parented to
            # the inbound traceparent (if any) so the HTTP client span
            # and the engine flame share one trace in Perfetto
            trace.record_span(
                "serving.request", req.submitted_perf,
                t_done - req.submitted_perf, trace_id=req.trace_id,
                parent_id=req.parent_span_id or None,
                span_id=req.root_span_id, request=req.id,
                tokens=len(sl.delivered), finish=finish,
                tenant=req.tenant or None)

    # ------------------------------------------------------------ hot reload
    def reload(self, step: int | None = None) -> int:
        """Hot swap to ``latest_valid_step()`` (or an explicit ``step`` —
        the online loop's rollback targets a specific previous
        generation) WITHOUT tearing any response: the new tree is
        restored off-thread and STAGED; the actual swap applies only at
        a resolve fence with every slot free (requests bound their own
        decode length, so the fence arrives within one request budget).
        While a swap is staged, admission pauses — queued requests wait
        and then decode wholly under the NEW generation; in-flight ones
        finish wholly under the OLD one, so every completion's
        ``generation``/``loaded_step`` stamp is exact.  Shapes are fixed
        by the config, so the swap hits the existing executables — no
        recompile.  With ``prefix_cache`` on, every cached chain is
        dropped atomically with the applied swap (its K/V was computed
        under the old weights); pages pinned only by the cache are wiped
        by the serve thread at its next fence before becoming
        allocatable again.  Returns the target step (applied, or staged
        for the next free fence)."""
        if self._ckpt is None:
            raise RuntimeError("no checkpoint attached — nothing to reload")
        target = step if step is not None else self._ckpt.latest_valid_step()
        if target is None:
            raise FileNotFoundError(
                f"no verified checkpoint under {self._ckpt.directory}")
        with self._lock:
            if target == self._loaded_step:
                # already serving it — and cancel any staged swap away
                # from it (a rollback racing an un-applied bad reload)
                self._staged = None
                return target
            if self._staged is not None and self._staged[2] == target:
                return target  # same target already parked for the fence
            template = self._raw_params
        with allow_transfers(), METRICS.time("serving.reload"):
            restored = self._ckpt.restore(template, step=target)
            new_params = self._maybe_quantize(restored["params"])
        with self._lock:
            self._staged = (restored["params"], new_params, target)
            applied = self._try_apply_staged_locked()
        METRICS.increment("serving.reloads")
        if applied:
            self._publish_generation_gauges()
        return target

    def _try_apply_staged_locked(self) -> bool:
        """Apply a staged swap iff NO request holds a slot (``_free`` at
        full capacity covers admitted-but-unregistered requests too: a
        slot pops off ``_free`` under this lock before its prefill ever
        reads params).  Caller holds ``self._lock``; gauge publication
        happens outside it (:meth:`_publish_generation_gauges`) to keep
        the registry lock un-nested."""
        if self._staged is None:
            return False
        if len(self._free) != self.cfg.slots:
            return False  # in-flight responses keep their generation
        raw, quantized, target = self._staged
        self._staged = None
        self._raw_params = raw
        self._params = quantized
        self._loaded_step = target
        self._generation += 1
        if self._pool is not None and self.cfg.prefix_cache:
            # same critical section as the swap: _admit's lookup (also
            # under this lock) can never see old-weight entries next to
            # the new params.  clear_prefix only QUARANTINES dead pages —
            # the serve thread wipes them at its next fence
            self._pending_wipe.extend(self._pool.clear_prefix())
        return True

    def _publish_generation_gauges(self) -> None:
        with self._lock:
            gen, step = self._generation, self._loaded_step
        METRICS.gauge("serving.generation", gen)
        if step is not None:
            METRICS.gauge("serving.loaded_step", step)

    # ------------------------------------------------- brownout actuators
    def set_speculative(self, enabled: bool) -> bool:
        """Brownout ladder level 1: turn speculative decoding off (or
        back on) at runtime.  Returns the new effective state.  Safe at
        any moment: the switch is read once per decode SEGMENT (a device
        fence), and the draft model only ever decided how many target
        tokens emit per dispatch — never which — so served tokens keep
        exact parity either way.  No-op on a plain engine."""
        if not self.cfg.speculative:
            return False
        with self._lock:
            self._spec_enabled = bool(enabled)
            now = self._spec_enabled
        METRICS.gauge("serving.speculative_enabled", 1.0 if now else 0.0)
        return now

    def set_max_new_cap(self, cap: int | None) -> None:
        """Brownout ladder level 2: clamp every future request's
        ``max_new_tokens`` to ``cap`` at admission (``None`` lifts the
        clamp).  In-flight requests keep their admitted budget."""
        if cap is not None and int(cap) < 1:
            raise ValueError(f"max_new cap must be >= 1, got {cap}")
        with self._lock:
            self._max_new_cap = int(cap) if cap is not None else None
        METRICS.gauge("serving.max_new_cap",
                      float(cap) if cap is not None else 0.0)

    def set_admission_hook(self, hook) -> None:
        """Install (or clear, with ``None``) an admission-side gate
        called with each validated :class:`GenerateRequest` BEFORE it
        enters the queue.  The hook rejects by raising a
        :class:`~.batcher.ServingRejected` subclass — the seam
        ``control/overload.py`` uses for per-tenant throttling and
        brownout shedding without serving importing control."""
        with self._lock:
            self._admission_hook = hook

    # ------------------------------------------------------------ stats
    def stats(self) -> dict:
        with self._lock:
            out = {
                "slots": self.cfg.slots,
                "active": len(self._slots),
                "free": len(self._free),
                "queue_depth": self._queue.depth(),
                "admitted": self._admitted,
                "completed": self._completed,
                "loaded_step": self._loaded_step,
                "generation": self._generation,
                "reload_staged": self._staged is not None,
                "prefill_buckets": sorted(self._admit_fns),
                "running": self._thread is not None,
                "warmed": self._warmed,
                "role": self.cfg.role,
                "speculative_enabled": (self.cfg.speculative
                                        and self._spec_enabled),
                "max_new_cap": self._max_new_cap,
            }
        if self._pool is not None:
            out["kv_pages"] = self._num_pages
            out["kv_quant"] = self.cfg.kv_quant
            out["kv_page_bytes"] = self._page_bytes
            out["kv_pages_in_use"] = self._pool.in_use()
            out["prefix_entries"] = self._pool.prefix_entries()
            out["prefix_hit_rate"] = self._pool.hit_rate()
            hits, lookups = self._pool.hit_counts()
            out["prefix_hits"] = hits
            out["prefix_lookups"] = lookups
        return out


class BatchScorer:
    """Coalesce concurrent single-row score calls into padded device
    batches through any row-wise pure ``fn`` (``net.output``, a zoo
    model's jitted apply, a ``partial(forward_local, ...)``).

    Rows queue through the same bounded :class:`RequestQueue` as
    generation (shared backpressure semantics); the worker pads each
    batch up to a power-of-two bucket (repeating the first row — pad
    outputs are discarded) so ``fn``'s jit cache sees at most
    ``log2(max_batch)`` shapes.
    """

    def __init__(self, fn: Callable, max_batch: int = 64,
                 max_queue: int = 256, max_batch_delay_ms: float = 2.0):
        self.fn = fn
        self.max_batch = max_batch
        self._queue = RequestQueue(max_queue, max_batch_delay_ms)
        self._shape_lock = threading.Lock()
        self._row_shape: tuple | None = None  # guarded-by: self._shape_lock
        self._row_dtype = None                # guarded-by: self._shape_lock
        self._buckets: set[int] = set()
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    def start(self) -> "BatchScorer":
        if self._thread is None:
            self._stop.clear()
            self._thread = threading.Thread(target=self._loop, daemon=True,
                                            name="serving-scorer")
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=30.0)
            self._thread = None
        for p in self._queue.drain():
            p._fail(RuntimeError("scorer stopped before request ran"))

    def __enter__(self) -> "BatchScorer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    def submit(self, x) -> PendingResult:
        x = np.asarray(x)
        # check-then-set must be atomic: two first submitters racing here
        # could each see None and publish different shapes
        with self._shape_lock:
            if self._row_shape is None:
                self._row_shape, self._row_dtype = x.shape, x.dtype
            elif x.shape != self._row_shape:
                raise ValueError(
                    f"row shape {x.shape} != first-seen {self._row_shape}")
        return self._queue.submit(ScoreRequest(x=x))

    def score(self, x, timeout: float = 30.0):
        """One row in, one output row out (blocking)."""
        return self.submit(x).result(timeout)

    def score_batch(self, xs, timeout: float = 30.0) -> np.ndarray:
        """Submit every row, gather in order — rows from concurrent
        callers interleave into shared device batches."""
        handles = [self.submit(x) for x in np.asarray(xs)]
        return np.stack([h.result(timeout) for h in handles])

    def _bucket(self, n: int) -> int:
        b = 1
        while b < n:
            b <<= 1
        return min(b, self.max_batch)

    def _loop(self) -> None:
        while not self._stop.is_set():
            batch = self._queue.take(self.max_batch, block_s=0.05)
            if not batch:
                continue
            try:
                self._run(batch)
            except Exception as e:
                METRICS.increment("serving.score.errors")
                for p in batch:
                    p._fail(e)

    def _run(self, batch: list[PendingResult]) -> None:
        n = len(batch)
        bucket = self._bucket(n)
        xs = np.stack([p.request.x for p in batch])
        if bucket > n:
            xs = np.concatenate(
                [xs, np.broadcast_to(xs[:1], (bucket - n,) + xs.shape[1:])])
        if bucket not in self._buckets:
            self._buckets.add(bucket)
            METRICS.increment("serving.score.recompile")
        with METRICS.time("serving.score_batch"):
            ys = np.asarray(self.fn(xs))
        METRICS.observe_time("serving.score.batch_fill", n / bucket,
                             buckets=FILL_BUCKETS)
        METRICS.increment("serving.score.rows", n)
        for i, p in enumerate(batch):
            p._complete(ys[i])
