"""TeraPipe: token-level pipeline parallelism as a shard_map program.

The paper's execution model (§3.2), adapted TPU-native (DESIGN.md §3):

* The layer stack is partitioned into K·V chunks; rank k of the ``pipe``
  mesh axis holds chunks ``k, K+k, …`` (global stage ``s = v·K + k``).
* A minibatch is cut into D microbatches × M token slices; work item
  i = d·M + m enters stage 0 at its scheduled tick and flows down the pipe,
  one ``collective-permute`` per tick.
* Each stage keeps a per-layer KV cache (or SSM/LRU state) of the prefix of
  the *current* microbatch it has already processed — the paper's attention
  context t_fwd(l, ctx).
* Stages run in SPMD lockstep: a tick is one program region bounded by the
  ppermute(s).

ONE executor, schedule-driven
-----------------------------

Which units run when — and how their inputs arrive — comes entirely from
the schedule IR (``core/schedules``): the executor is a single rolled
``lax.scan`` tick loop that INTERPRETS

* the **tick table** ``(tick, rank) -> (work_item, chunk, kind)`` — the
  per-tick unit kind (idle / fwd / fused bwd / split B / split W)
  dispatches a ``lax.switch`` (fwd-only tables, which are differentiated
  through the loop, compute every tick and mask idle ones instead); the
  chunk index gathers the rank's per-chunk params/caches (shape-stable
  ``dynamic_index_in_dim`` from the rank-major chunk stacks, so the body
  traces ONCE regardless of D·M·V);
* the **comm plan** (``StageAssignment.comm_plan``) — whether the reverse
  cotangent ring fires, the *skew hold* of each ring: wrap-around chunk
  handoffs (global stage ``v·K+K-1 -> (v+1)·K``) ride their ring one hop
  and then sit ``hold`` ticks in a destination-side skew ring buffer
  (depth ``hold+1``, pushed every tick, read at slot ``(t - hold) mod
  (hold+1)``) before their consumer tick — and the reverse ring's *lag*:
  ``rev_lag > 0`` makes EVERY rank read its cotangent ``lag`` ticks after
  delivery (ZB-H1's dilation-3 spacing), via the same gskew buffer;
* the **residual geometry** (``residual_spread``) — explicit-bwd schedules
  save each fwd unit's inputs in a ``(V, R)`` ring buffer (collision-free
  by the IR audit) and retire them at the unit's retiring backward tick:
  the fused bwd, or the W unit when the schedule splits the backward (B
  reads the slot but keeps it live; B additionally saves the output
  cotangent it consumed in a second ``(V, R)`` buffer for W to replay).

Schedules select behavior through IR properties only — there is no
per-schedule executor code.  The five registered schedules:

* ``contiguous`` (V=1) — the paper's TeraPipe schedule; backward via
  whole-program autodiff (live activations grow with D·M).
* ``interleaved`` (V≥2) — Megatron virtual pipeline; fill/drain bubble ~V×
  smaller; autodiff backward (live activations O(D·M·V)).
* ``1f1b`` — explicit bwd units (``schedules.OneFOneB``): each bwd unit is
  a per-unit ``jax.vjp`` rebuilt from the saved inputs (stage-granular
  recompute), grads accumulate in the scan carry, cotangents ride a second
  REVERSE ppermute ring; peak live activations ``min(D·M, K+M-1)``.
* ``interleaved-1f1b`` (V≥2) — the 1F1B unit ordering over V chunks with
  K-tick skew buffers on both rings' wrap edges: interleaving's smaller
  bubble AND the flat-in-D memory bound.  Pure IR — the executor needed no
  changes to run it.
* ``zb-h1`` (V=1) — zero-bubble ZB-H1 (``schedules.ZeroBubbleH1``): each
  bwd unit splits into a B (``jax.vjp`` over the unit's *inputs* — the
  cotangent leaves on the reverse ring immediately) and a same-rank W one
  tick later (``jax.vjp`` over the *params*, replaying the saved residual
  against the output cotangent B consumed).  The reverse ring runs with
  ``rev_lag = 1``; W fills what 1F1B spends as drain bubble.

For fwd-only schedules the scan is a differentiable loss
(:func:`make_terapipe_loss`, wrapped in ``jax.value_and_grad``); for
explicit-bwd schedules the SAME tick interpreter computes loss AND grads in
one program.  :func:`make_terapipe_value_and_grad` is the one entry point
train/dryrun drive for every schedule.

Within a stage, optional Megatron-style tensor parallelism over a ``tp``
mesh axis: weights arrive head/ff/expert-sharded and the block fns psum
partial outputs (see models/* with cfg.tp_axis).  (Not yet supported for
explicit-bwd schedules — the per-slice head loss and explicit grad psums
need per-leaf tp-aware reductions.)

GPipe (the paper's baseline) is the D>1, M=1 special case.

Executor design notes (rolled tick loop)
----------------------------------------

The tick loop is ROLLED with ``jax.lax.scan`` over the tick index, so XLA
traces and compiles ONE tick program regardless of the tick count — the
large-M schemes the DP planner (§3.3) emits stay cheap to trace/compile.
The tick's unit is gathered from the (host-precomputed) tick table with the
traced tick index; all branches are shape-stable.

* Double-buffered send/recv: the ``ppermute`` on the outgoing value is
  issued as soon as the stage output exists, BEFORE the outbuf write (and
  the cache merge) — those consume the previous buffer generation, so XLA's
  async collective-permute-start/-done pair overlaps the wire transfer with
  the trailing per-tick bookkeeping.
* Cache mutation is gated on the unit kind: idle (fill/drain) ticks leave
  the cache carry BIT-IDENTICAL, and bwd ticks never touch the forward
  cache (they thread a separate per-chunk cotangent cache).
* Requirement on block fns: shape-stable across ticks (every slice runs in
  an ``l_max``-padded buffer; ``ctx`` is traced, so attention uses the
  ``sliced_dyn`` dynamic-slice path).

``TeraPipeConfig.unroll=True`` is the escape hatch: the SAME tick body is
Python-unrolled (one jaxpr copy per tick) for differential testing and for
inspecting a single tick's HLO.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import tracing
from repro.core.schedules import (KIND_BWD_INPUT, KIND_BWD_WEIGHT, KIND_FWD, get_schedule, interleave_stacked, schedule_names, uninterleave_stacked)
from repro.models import Model, build_model
from repro.models.common import ModelConfig, rms_norm
from repro.models.lm import _scan_full

# logical axis -> pipeline mesh axis mapping for TP-sharded stage weights
_TP_LOGICAL = ("heads", "ff", "experts")

#: registered schedule names (core/schedules registry) — the CLI choices
SCHEDULES = schedule_names()


@dataclasses.dataclass
class TeraPipeConfig:
    n_token_slices: int = 4          # M (uniform mode; ignored if slice_lens)
    # non-uniform DP scheme (the paper's Alg. 1 output): static slice lengths
    # summing to seq_len.  Executed with l_max-padded buffers; garbage tail
    # positions of short slices are overwritten in the KV cache by the next
    # slice before ever being read, and discarded at reassembly (DESIGN §3).
    # Attention-family archs only (state-based families need uniform slices).
    slice_lens: Optional[Tuple[int, ...]] = None
    n_microbatches: int = 1          # D
    pipe_axis: str = "pipe"
    tp_axis: Optional[str] = None    # None => no TP within a stage
    data_axes: Tuple[str, ...] = ("data",)
    cache_dtype: Any = jnp.bfloat16
    # Python-unroll the tick loop (one jaxpr copy per tick) instead of the
    # rolled lax.scan executor.  Trace/compile cost grows with the tick
    # count; differential-testing / HLO-inspection escape hatch only.
    unroll: bool = False
    # V: virtual pipeline stages (Megatron-LM interleaving, via the schedule
    # IR in core/schedules).  Each rank holds V non-contiguous layer chunks
    # (round-robin over the K*V global stages); V>1 requires D*M divisible
    # by the pipe degree K (work items advance in ring groups of K).
    virtual_stages: int = 1
    # which schedule table drives the tick loop (core/schedules registry);
    # "contiguous" with virtual_stages>1 is promoted to "interleaved" for
    # back-compat
    schedule: str = "contiguous"
    # debug: extra all-idle ticks appended to the tick loop.  With correctly
    # gated cache mutation they are exact no-ops (tests assert bit-identical
    # final caches); never needed in production.
    extra_ticks: int = 0
    # route stage attention through the Pallas flash kernels (fused fwd+bwd,
    # traced-ctx scalar prefetch — see repro.kernels).  None defers to the
    # ModelConfig's own ``use_kernel``; True/False overrides it for the
    # stage-local model the executor runs (fwd-only schedules differentiate
    # through the kernel's custom_vjp; explicit-bwd schedules' per-unit
    # jax.vjp hits the fused backward kernels inside every steady-state
    # tick).
    use_kernel: Optional[bool] = None


def _group_split(model: Model):
    """(pre_groups, main_group, post_groups) — only the (single, homogeneous)
    main group is pipelined; small pre/post groups run under plain GSPMD
    around the pipeline (DESIGN.md §3)."""
    gs = model.groups
    if model.cfg.family == "encdec":
        raise NotImplementedError(
            "enc-dec archs: the bidirectional encoder is not token-sliceable "
            "(paper footnote 1); pipeline the decoder via the generic path or "
            "use GSPMD mode")
    if len(gs) == 1:
        return [], gs[0], []
    if model.cfg.family == "moe":        # [dense0?, moe]
        return list(gs[:-1]), gs[-1], []
    if model.cfg.family == "hybrid":     # [super, tail?]
        return [], gs[0], list(gs[1:])
    raise NotImplementedError(model.cfg.family)


def _leaf_pspec(spec: Tuple, tp_axis, tp_size: int, pipe_axis, cfg: ModelConfig):
    """PartitionSpec for one stacked main-group param leaf.

    spec[0] is the layer axis (-> pipe); 'heads'/'ff'/'experts' -> tp;
    'kv_heads' -> tp only if divisible; everything else replicated.
    """
    out = [pipe_axis]
    for ax in spec[1:]:
        if tp_axis and tp_size > 1 and ax in _TP_LOGICAL:
            out.append(tp_axis)
        elif (tp_axis and tp_size > 1 and ax == "kv_heads"
              and cfg.n_kv_heads % tp_size == 0):
            out.append(tp_axis)
        else:
            out.append(None)
    return P(*out)


class _Plan:
    """Everything the executor derives from (model, mesh, tcfg, shapes):
    slice geometry, schedule assignment, local model, param specs."""

    def __init__(self, model: Model, specs, mesh: Mesh, tcfg: TeraPipeConfig,
                 seq_len: int, global_batch: int):
        cfg = model.cfg
        self.model, self.cfg, self.mesh, self.tcfg = model, cfg, mesh, tcfg
        self.K = K = mesh.shape[tcfg.pipe_axis]
        self.tp = tp = mesh.shape[tcfg.tp_axis] if tcfg.tp_axis else 1
        data = 1
        for a in tcfg.data_axes:
            data *= mesh.shape[a]
        self.data = data
        self.D = D = tcfg.n_microbatches
        self.L, self.B = L, B = seq_len, global_batch

        sched = tcfg.schedule
        V = tcfg.virtual_stages
        if sched == "contiguous" and V > 1:
            sched = "interleaved"    # back-compat: V>1 implies interleaving
        self.sched, self.V = sched, V

        if tcfg.slice_lens is not None:
            slice_lens = tuple(tcfg.slice_lens)
            assert sum(slice_lens) == L, (slice_lens, L)
            M = len(slice_lens)
            l = max(slice_lens)                  # padded slice buffer length
            uniform = all(s == l for s in slice_lens)
            if not uniform:
                assert cfg.family in ("dense", "vlm", "moe"), \
                    "non-uniform slices need prefix-overwrite semantics (KV " \
                    "caches); state-based families require uniform slices"
            starts = [0]
            for s in slice_lens[:-1]:
                starts.append(starts[-1] + s)
        else:
            M = tcfg.n_token_slices
            assert L % M == 0, (L, M)
            l = L // M
            slice_lens = tuple([l] * M)
            starts = [i * l for i in range(M)]
        self.slice_lens, self.M, self.l = slice_lens, M, l
        self.starts, self.uniform = starts, all(s == l for s in slice_lens)
        assert B % (data * D) == 0, (B, data, D)
        self.mb_local = B // (data * D)
        self.b_local = B // data
        self.d_model = cfg.d_model

        self.pre, self.main, self.post = _group_split(model)
        n_main = self.main.count
        # the registry validates the (schedule, V) combination and builds
        # the IR value the executor interprets
        self.assign = get_schedule(sched, n_ranks=K, n_layers=n_main,
                                   virtual_stages=V, n_microbatches=D)
        self.bps = self.assign.blocks_per_chunk
        self.n_pad = self.assign.n_pad
        self.n_main = n_main

        # local-config model: block fns see TP-local head counts in shard_map
        if tcfg.use_kernel is not None:
            cfg = cfg.replace(use_kernel=tcfg.use_kernel)
        if tp > 1:
            assert cfg.n_heads % tp == 0, (cfg.n_heads, tp)
            kv_local = (cfg.n_kv_heads // tp if cfg.n_kv_heads % tp == 0
                        else cfg.n_kv_heads)
            cfg_local = cfg.replace(tp_axis=tcfg.tp_axis,
                                    head_dim=cfg.hd,    # pin: hd derives from
                                    n_heads=cfg.n_heads // tp,  # n_heads else
                                    n_kv_heads=kv_local)
        else:
            cfg_local = cfg
        model_local = build_model(cfg_local)
        self.main_local = next(g for g in model_local.groups
                               if g.name == self.main.name)
        self.block_fn = self.main_local.sliced_dyn or self.main_local.sliced

        main_spec_tree = specs["groups"][self.main.name]
        self.is_spec = is_spec = lambda s: isinstance(s, tuple)
        self.stage_in_specs = jax.tree.map(
            lambda s: _leaf_pspec(s, tcfg.tp_axis, tp, tcfg.pipe_axis, cfg),
            main_spec_tree, is_leaf=is_spec)

        # batch activations: sharded over data axes, replicated over pipe/tp
        self.x_spec = P(tcfg.data_axes, None, None)
        self.DM = DM = D * M
        if V > 1:
            assert DM % K == 0, (
                f"virtual_stages={V} needs D*M = {D}*{M} = {DM} divisible by "
                f"the pipe degree K={K}: interleaved work items advance in "
                f"ring groups of K (see core/schedules)")
        # padded caches: a short slice's garbage tail may write up to l
        # beyond its ctx; pad the cache so the LAST slice's tail never wraps
        # onto valid entries (overwritten-before-read invariant, DESIGN §3)
        self.cache_len = L if self.uniform else L + l

    @jax.named_scope(tracing.EMBED)
    def prefix(self, params, batch):
        """Shared pre-pipeline prologue: embed -> pre groups -> activation
        dtype -> (non-uniform) seq pad so a short slice's l_max-window never
        clamps (dynamic_slice clamps OOB starts, which would alias real
        data).  Pure in (params, batch) — the explicit-bwd path
        differentiates it with jax.vjp for the embedding/pre-group grads."""
        x = self.model.embed(params, batch, 0)
        for g in self.pre:
            x = _scan_full(g, params["groups"][g.name], x, self.cfg.remat)
        x = x.astype(self.cfg.dtype)
        if not self.uniform:
            x = jnp.pad(x, ((0, 0), (0, self.l), (0, 0)))
        return x

    def stage_apply(self, params_c, x, caches_c, ctx):
        """One layer-chunk forward (scan over the chunk's blocks)."""
        block_fn, remat = self.block_fn, self.cfg.remat

        def body(h, inp):
            bp_l, c_l = inp
            h, c_l = block_fn(bp_l, h, c_l, ctx)
            return h, c_l
        body_fn = jax.checkpoint(body) if remat else body
        x, caches_c = jax.lax.scan(body_fn, x, (params_c, caches_c))
        return x, caches_c

    def init_stage_caches(self, lead: Tuple[int, ...]):
        """Zero per-chunk cache pytree with the given leading axes."""
        cache_struct = jax.eval_shape(
            lambda: self.main_local.init_cache(
                self.mb_local, self.cache_len, self.tcfg.cache_dtype))
        return jax.tree.map(
            lambda a: jnp.zeros(lead + a.shape[1:], a.dtype), cache_struct)

    def prep_stage_params(self, stage_params):
        """Pad the stacked main group to the schedule's row count and (V>1)
        reorder rank-major, constrained straight to the pipe-sharded layout.

        NB: jnp.pad, not concatenate-with-zeros: XLA once mispartitioned the
        concat feeding a shard_map operand on multi-axis meshes (data>1 x
        pipe: garbage stage params).  interleave_stacked is reshape+swapaxes
        for the same reason."""
        if not (self.n_pad or self.V > 1):
            return stage_params

        def _prep(a, sp):
            if self.n_pad:
                a = jnp.pad(a, ((0, self.n_pad),) + ((0, 0),) * (a.ndim - 1))
            if self.V > 1:
                a = interleave_stacked(a, self.assign)
            return jax.lax.with_sharding_constraint(
                a, NamedSharding(self.mesh, sp))
        return jax.tree.map(_prep, stage_params, self.stage_in_specs)

    def param_shardings_fn(self):
        tcfg, cfg, mesh = self.tcfg, self.cfg, self.mesh
        n_main, K, tp, is_spec = self.n_main, self.K, self.tp, self.is_spec
        main_name = self.main.name

        def param_shardings(params_tree_specs):
            """NamedSharding tree for jit in_shardings (stage params
            pipe-sharded, everything else replicated/TP per logical spec)."""
            # main group: pipe on layer axis (+tp); others replicated.  When
            # the UNPADDED stack is not divisible by the pipe degree (e.g.
            # gpt3-1b's 24 layers on pipe=16) a pipe-sharded in_sharding
            # would be rejected at the jit boundary — keep the layer axis
            # replicated there and let the loss re-shard at the pad boundary
            # (the with_sharding_constraint in prep_stage_params).
            def build(spec, in_main):
                if in_main:
                    ps = _leaf_pspec(spec, tcfg.tp_axis, tp, tcfg.pipe_axis,
                                     cfg)
                    if n_main % K:
                        ps = P(None, *tuple(ps)[1:])
                    return NamedSharding(mesh, ps)
                return NamedSharding(mesh, P())
            out = {}
            for key, sub in params_tree_specs.items():
                if key == "groups":
                    out["groups"] = {
                        gname: jax.tree.map(
                            lambda s: build(s, gname == main_name),
                            gspec, is_leaf=is_spec)
                        for gname, gspec in sub.items()}
                else:
                    out[key] = jax.tree.map(
                        lambda s: NamedSharding(mesh, P()), sub,
                        is_leaf=is_spec)
            return out
        return param_shardings


# ---------------------------------------------------------------------------
# THE executor: one rolled tick loop interpreting the schedule IR
# ---------------------------------------------------------------------------
def _make_pipeline_body(p: _Plan):
    """Build the per-device scan program interpreting ``p.assign``.

    Returns ``pipeline_body`` whose signature follows the schedule class:

    * fwd-only tables: ``(stage_params, x_emb) -> (outbuf, final_caches)``
      — a differentiable forward; the loss wrapper reassembles the last
      rank's outputs and autodiff provides the backward.
    * explicit-bwd tables: ``(stage_params, head_p, x_emb, labels) ->
      (loss, d_stage, d_ln, d_wh, d_emb)`` — loss AND grads in one program
      (per-unit vjp at bwd ticks; never differentiated again).

    Everything else — unit decode, chunk gather, comm (rings + skew
    buffers), cache freshness, residual save/retire — is one code path
    driven by the tick table and comm plan.
    """
    tcfg, cfg = p.tcfg, p.cfg
    assign = p.assign
    K, V, M, l, DM = p.K, p.V, p.M, p.l, p.DM
    mb_local, d_model = p.mb_local, p.d_model
    bps = p.bps
    has_bwd = assign.has_backward
    plan = assign.comm_plan()

    tab = assign.tick_table(DM)                      # (T, K, 3), host-side
    if tcfg.extra_ticks:                             # debug: trailing idles
        pad = np.full((tcfg.extra_ticks, K, 3), -1, tab.dtype)
        tab = np.concatenate([tab, pad])
    ticks = tab.shape[0]
    items_np, chunk_np, kcol_np = tab[..., 0], tab[..., 1], tab[..., 2]
    tracing.count(tracing.TICKS, ticks)
    tracing.count(tracing.RANKS, K)
    tracing.count(tracing.IDLE_UNITS, np.sum(items_np < 0))
    splits = assign.splits_backward
    # per-(tick, rank) switch branch: 0 = idle, 1 = fwd, then the backward
    # arms — fused tables get one bwd branch (2); split tables get
    # bwd-input (2) and bwd-weight (3).  No dead branches either way.
    if splits:
        branch_np = np.select(
            [items_np < 0, kcol_np == KIND_FWD, kcol_np == KIND_BWD_INPUT,
             kcol_np == KIND_BWD_WEIGHT], [0, 1, 2, 3])
    else:
        branch_np = np.where(items_np < 0, 0, 1 + np.maximum(kcol_np, 0))
    chunk_np = np.clip(chunk_np, 0, V - 1)
    R = assign.residual_spread(DM) if has_bwd else 0
    assert not (plan.rev_hold and plan.rev_lag), (
        "rev_hold (wrap-edge skew) and rev_lag (all-edge lag) are mutually "
        "exclusive in the executor's gskew buffer; no schedule needs both")
    Hx = plan.fwd_hold + 1                           # skew buffer depths
    Hg = max(plan.rev_hold, plan.rev_lag) + 1
    starts_host, lens_host = p.starts, list(p.slice_lens)
    uniform = p.uniform
    inv_total = 1.0 / float(p.B * p.L)
    fwd_perm = [(j, (j + 1) % K) for j in range(K)]
    rev_perm = [(j, (j - 1) % K) for j in range(K)]

    @jax.named_scope(tracing.HEAD_LOSS)
    def slice_loss(x_out, head_p, labels_sl, mask):
        """Per-slice LM loss contribution, pre-normalized by the GLOBAL
        token count (so the accumulated sum is the mean loss and a unit
        seed yields correctly scaled grads).  Matches models.lm math:
        rms_norm -> head matmul in activation dtype -> f32 xent."""
        final_ln, w_head = head_p
        h = rms_norm(x_out, final_ln)
        logits = (h @ w_head.astype(h.dtype)).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels_sl[..., None],
                                   axis=-1)[..., 0]
        return jnp.sum((logz - gold) * mask) * inv_total

    def pipeline_body(stage_params, x_emb, head_p=None, labels=None):
        k_rank = jax.lax.axis_index(tcfg.pipe_axis)
        starts_arr = jnp.asarray(starts_host, jnp.int32)
        lens_arr = jnp.asarray(lens_host, jnp.int32)
        items_tab = jnp.asarray(items_np, jnp.int32)
        chunk_tab = jnp.asarray(chunk_np, jnp.int32)
        branch_tab = jnp.asarray(branch_np, jnp.int32)
        # the local stack arrives rank-major chunk order: (V*bps, ...) ->
        # (V, bps, ...) so a tick can gather its chunk shape-stably
        stage_params_c = jax.tree.map(
            lambda a: a.reshape((V, bps) + a.shape[1:]), stage_params)
        caches0 = p.init_stage_caches((V, bps))

        def read_tab(table, t):
            row = jax.lax.dynamic_index_in_dim(table, t, 0, keepdims=False)
            return jax.lax.dynamic_index_in_dim(row, k_rank, 0,
                                                keepdims=False)

        def chunk_of(tree, v_idx):
            # V == 1: the chunk index is the host constant 0 — a static
            # squeeze instead of a traced gather keeps the V=1 schedules'
            # trace cost at the pre-chunk-machinery level
            if V == 1:
                return jax.tree.map(lambda a: a[0], tree)
            return jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, v_idx, 0,
                                                       keepdims=False), tree)

        @jax.named_scope(tracing.CACHE)
        def put_chunk(tree, sub, v_idx):
            if V == 1:
                return jax.tree.map(lambda a, c: c[None], tree, sub)
            return jax.tree.map(
                lambda a, c: jax.lax.dynamic_update_index_in_dim(a, c, v_idx,
                                                                 0),
                tree, sub)

        def tree_where(pred, a, b):
            # a cond, not jnp.where: under autodiff a where saves its
            # predicate broadcast to the full (cache-sized) shape per tick
            return jax.lax.cond(pred, lambda: a, lambda: b)

        def tick(carry, t):
            """One pipeline tick.  ``t`` is traced — the body is shape-
            stable in the tick index, so it traces ONCE under the rolled
            executor; the unit comes from the gathered tick table."""
            i_raw = read_tab(items_tab, t)
            # V == 1 schedules have exactly one chunk: pin the index to the
            # literal 0 so every chunk-indexed op below folds to a static
            # slice/update (no traced-gather overhead on the V=1 hot path)
            v_idx = read_tab(chunk_tab, t) if V > 1 else 0
            branch = read_tab(branch_tab, t)
            i_c = jnp.clip(i_raw, 0, DM - 1)
            mb_idx, sl_idx = i_c // M, i_c % M
            ctx = jnp.take(starts_arr, sl_idx) if not uniform \
                else sl_idx * l
            # comm bookkeeping first: every received ring value lands in the
            # skew buffers (slot t mod H), idle ticks included — wrap
            # handoffs are read back ``hold`` ticks later (and under
            # rev_lag, EVERY reverse delivery is read ``lag`` ticks later)
            if plan.fwd_hold:
                carry = dict(carry, xskew=jax.lax.dynamic_update_index_in_dim(
                    carry["xskew"], carry["x"], t % Hx, 0))
            if has_bwd and Hg > 1:
                carry = dict(carry, gskew=jax.lax.dynamic_update_index_in_dim(
                    carry["gskew"], carry["g"], t % Hg, 0))
            # forward input: rank 0 chunk 0 admits new work; rank 0 chunk
            # v>0 consumes the wrap-around handoff (skew-held when the comm
            # plan says so); everyone else reads the ring fresh
            x0 = jax.lax.dynamic_slice(
                x_emb, (mb_idx * mb_local, ctx, 0), (mb_local, l, d_model))
            if plan.fwd_hold:
                x_wrap = jax.lax.dynamic_index_in_dim(
                    carry["xskew"], (t - plan.fwd_hold) % Hx, 0,
                    keepdims=False)
                x_ring = jnp.where(k_rank == 0, x_wrap, carry["x"])
            else:
                x_ring = carry["x"]
            x_in = jnp.where((k_rank == 0) & (v_idx == 0), x0, x_ring)
            params_c = chunk_of(stage_params_c, v_idx)
            caches_c = chunk_of(carry["caches"], v_idx)
            # new microbatch => fresh prefix: zero the chunk's caches.
            # Required for state-based families (SSM/LRU carry real state);
            # harmless and exact for KV caches (masked by absolute
            # positions anyway).  Only applied inside the fwd branch — idle
            # and bwd ticks must not mutate cache state.
            fresh = sl_idx == 0
            with jax.named_scope(tracing.CACHE):
                caches_in = tree_where(fresh,
                                       jax.tree.map(jnp.zeros_like, caches_c),
                                       caches_c)

            @jax.named_scope(tracing.CACHE)
            def save_slot(c, keys, x, tree):
                """Write a unit's activation and cache tree into its slot of
                the (V, R) ring buffers ``keys`` (see residual_spread)."""
                kx, kt = keys
                slot = i_c % R
                return dict(c, **{
                    kx: jax.lax.dynamic_update_slice(
                        c[kx], x[None, None], (v_idx, slot, 0, 0, 0)),
                    kt: jax.tree.map(
                        lambda buf, a: jax.lax.dynamic_update_slice(
                            buf, a[None, None], (v_idx, slot) + (0,) * a.ndim),
                        c[kt], tree)})

            @jax.named_scope(tracing.CACHE)
            def read_slot(c, keys):
                """The unit's activation and cache tree from its slot of the
                (V, R) ring buffers ``keys``."""
                kx, kt = keys
                slot = i_c % R
                x = jax.lax.dynamic_slice(
                    c[kx], (v_idx, slot, 0, 0, 0),
                    (1, 1, mb_local, l, d_model))[0, 0]
                tree = jax.tree.map(
                    lambda buf: jax.lax.dynamic_slice(
                        buf, (v_idx, slot) + (0,) * (buf.ndim - 2),
                        (1, 1) + buf.shape[2:])[0, 0], c[kt])
                return x, tree

            def idle_branch(c):
                return c

            @jax.named_scope(tracing.TICK_FWD)
            def fwd_branch(c):
                x_out, caches_out = p.stage_apply(params_c, x_in, caches_in,
                                                  ctx)
                c = dict(c, x=x_out,
                         caches=put_chunk(c["caches"], caches_out, v_idx))
                if has_bwd:
                    # save the unit's inputs for its bwd tick's recompute;
                    # the slot is released at the retiring backward tick
                    # (fused bwd, or W when the schedule splits the backward
                    # — B only reads it)
                    c = save_slot(c, ("rx", "rc"), x_in, caches_in)
                return c

            if has_bwd:
                labels_sl = jax.lax.dynamic_slice(
                    labels, (mb_idx * mb_local, ctx), (mb_local, l))
                mask = (jnp.arange(l) < jnp.take(lens_arr, sl_idx))[None, :]
                is_last = (k_rank == K - 1) & (v_idx == V - 1)
                if plan.rev_hold:
                    g_wrap = jax.lax.dynamic_index_in_dim(
                        carry["gskew"], (t - plan.rev_hold) % Hg, 0,
                        keepdims=False)
                    g_ring = jnp.where(k_rank == K - 1, g_wrap, carry["g"])
                elif plan.rev_lag:
                    # all-edge lag: EVERY rank consumes its cotangent
                    # ``rev_lag`` ticks after the ring delivered it
                    g_ring = jax.lax.dynamic_index_in_dim(
                        carry["gskew"], (t - plan.rev_lag) % Hg, 0,
                        keepdims=False)
                else:
                    g_ring = carry["g"]
                # the last global stage seeds from its own loss, not the ring
                g_cot = jnp.where(is_last, jnp.zeros_like(g_ring), g_ring)
                seed = jnp.where(is_last, jnp.float32(1), jnp.float32(0))

                def unit(sp, xi, ci, hp):
                    xo, co = p.stage_apply(sp, xi, ci, ctx)
                    return xo, co, slice_loss(xo, hp, labels_sl, mask)

                def out_cotangent(c):
                    """(d_xo, d_co, d_loss) cotangent of the unit's outputs:
                    the ring-delivered activation cotangent, the accumulated
                    downstream-slice cache cotangent (zeroed at the first
                    bwd of a microbatch, slice M-1), and the loss seed."""
                    first_bwd = sl_idx == M - 1
                    gcache_c = chunk_of(c["gcache"], v_idx)
                    gcache_in = tree_where(
                        first_bwd, jax.tree.map(jnp.zeros_like, gcache_c),
                        gcache_c)
                    return g_cot, gcache_in, seed

                def apply_input_cots(c, d_x_in, d_c_in, ls):
                    """Input-side results into the carry: the cotangent onto
                    the reverse ring, the cache-cotangent accumulator, the
                    embedding cotangent (only rank 0 chunk 0's d(x_in)
                    belongs to x_emb — everyone else's went down the ring),
                    and the loss term."""
                    add = jnp.where((k_rank == 0) & (v_idx == 0), d_x_in,
                                    jnp.zeros_like(d_x_in))
                    seg = jax.lax.dynamic_slice(
                        c["d_emb"], (mb_idx * mb_local, ctx, 0),
                        (mb_local, l, d_model))
                    d_emb2 = jax.lax.dynamic_update_slice(
                        c["d_emb"], seg + add, (mb_idx * mb_local, ctx, 0))
                    return dict(
                        c, g=d_x_in,
                        gcache=put_chunk(c["gcache"], d_c_in, v_idx),
                        d_emb=d_emb2,
                        loss=c["loss"] + jnp.where(is_last, ls,
                                                   jnp.float32(0)))

                def apply_param_cots(c, d_sp, d_hp):
                    """Param-side results into the carry: stage-chunk and
                    head grads."""
                    d_stage2 = jax.tree.map(
                        lambda acc, g: acc.at[v_idx].add(g),
                        c["d_stage"], d_sp)
                    return dict(c, d_stage=d_stage2,
                                d_ln=c["d_ln"] + d_hp[0],
                                d_wh=c["d_wh"] + d_hp[1])

                @jax.named_scope(tracing.TICK_BWD)
                def bwd_branch(c):
                    """Fused backward: one vjp over params AND inputs."""
                    x_saved, c_saved = read_slot(c, ("rx", "rc"))
                    (_, _, ls), vjp = jax.vjp(unit, params_c, x_saved,
                                              c_saved, head_p)
                    d_sp, d_x_in, d_c_in, d_hp = vjp(out_cotangent(c))
                    return apply_param_cots(
                        apply_input_cots(c, d_x_in, d_c_in, ls), d_sp, d_hp)

                @jax.named_scope(tracing.TICK_BWD_INPUT)
                def bwd_input_branch(c):
                    """B: vjp over the unit's INPUTS only — the cotangent
                    leaves on the reverse ring THIS tick; the output
                    cotangent it consumed is saved for the matching W."""
                    x_saved, c_saved = read_slot(c, ("rx", "rc"))
                    (_, _, ls), vjp = jax.vjp(
                        lambda xi, ci: unit(params_c, xi, ci, head_p),
                        x_saved, c_saved)
                    d_xo, d_co, d_ls = out_cotangent(c)
                    d_x_in, d_c_in = vjp((d_xo, d_co, d_ls))
                    c = save_slot(c, ("rg", "rgc"), d_xo, d_co)
                    return apply_input_cots(c, d_x_in, d_c_in, ls)

                @jax.named_scope(tracing.TICK_BWD_WEIGHT)
                def bwd_weight_branch(c):
                    """W: vjp over the unit's PARAMS (stage chunk + head),
                    replaying the saved residual against the output
                    cotangent its B consumed; releases the residual slot.
                    The loss seed recomputes from is_last — only the
                    array-shaped cotangents need saving."""
                    x_saved, c_saved = read_slot(c, ("rx", "rc"))
                    g_saved, gc_saved = read_slot(c, ("rg", "rgc"))
                    _, vjp = jax.vjp(
                        lambda sp, hp: unit(sp, x_saved, c_saved, hp),
                        params_c, head_p)
                    d_sp, d_hp = vjp((g_saved, gc_saved, seed))
                    return apply_param_cots(c, d_sp, d_hp)

                if splits:
                    out = jax.lax.switch(
                        branch, (idle_branch, fwd_branch, bwd_input_branch,
                                 bwd_weight_branch), carry)
                else:
                    out = jax.lax.switch(branch, (idle_branch, fwd_branch,
                                                  bwd_branch), carry)
            else:
                # fwd-only schedules are differentiated through this scan,
                # so every tick computes and the merge is masked: a switch
                # skipping idle ticks would make the stage params a residual
                # of the switch, stacked once per tick (T copies of the
                # stage's weights).  In SPMD lockstep an idle rank waits for
                # the busy ones anyway, so the tick takes no longer.
                out = tree_where(branch > 0, fwd_branch(carry), carry)
            # activations ride the forward ring (issued BEFORE the trailing
            # bookkeeping below so the async collective overlaps it);
            # cotangents ride the reverse ring.  Consumers read a ring value
            # only on the tick the schedule delivers it (validate()), so
            # off-kind sends are inert.
            x_send = out["x"]
            with jax.named_scope(tracing.RING):
                x_next = jax.lax.ppermute(x_send, tcfg.pipe_axis, fwd_perm)
                out = dict(out, x=x_next)
                if has_bwd:
                    out = dict(out, g=jax.lax.ppermute(
                        out["g"], tcfg.pipe_axis, rev_perm))
            if not has_bwd:
                # per-work-item output ring written by every rank; only the
                # last rank's rows are read.  Idle ticks land in the dump
                # row DM; under interleaving an item's writes ascend in
                # chunk order, so the final chunk V-1 lands last.
                row = jnp.where(branch > 0, i_c, DM)
                with jax.named_scope(tracing.REASSEMBLE):
                    out = dict(out, out=jax.lax.dynamic_update_slice(
                        out["out"], x_send[None], (row, 0, 0, 0)))
            return out, None

        carry = {
            "x": jnp.zeros((mb_local, l, d_model), cfg.dtype),
            "caches": caches0,
        }
        if plan.fwd_hold:
            carry["xskew"] = jnp.zeros((Hx, mb_local, l, d_model), cfg.dtype)
        if has_bwd:
            carry["g"] = jnp.zeros((mb_local, l, d_model), cfg.dtype)
            if Hg > 1:
                carry["gskew"] = jnp.zeros((Hg, mb_local, l, d_model),
                                           cfg.dtype)
            carry["gcache"] = jax.tree.map(jnp.zeros_like, caches0)
            carry["rx"] = jnp.zeros((V, R, mb_local, l, d_model), cfg.dtype)
            carry["rc"] = jax.tree.map(
                lambda a: jnp.zeros((V, R) + a.shape[1:], a.dtype), caches0)
            if splits:
                # output cotangents B consumed, replayed by W: same (V, R)
                # ring-buffer geometry as the fwd residuals (a unit's slot
                # is written at B and released at W)
                carry["rg"] = jnp.zeros((V, R, mb_local, l, d_model),
                                        cfg.dtype)
                carry["rgc"] = jax.tree.map(
                    lambda a: jnp.zeros((V, R) + a.shape[1:], a.dtype),
                    caches0)
            carry["d_stage"] = jax.tree.map(jnp.zeros_like, stage_params_c)
            carry["d_ln"] = jnp.zeros_like(head_p[0])
            carry["d_wh"] = jnp.zeros_like(head_p[1])
            carry["d_emb"] = jnp.zeros_like(x_emb)
            carry["loss"] = jnp.float32(0)
        else:
            carry["out"] = jnp.zeros((DM + 1, mb_local, l, d_model),
                                     cfg.dtype)

        if tcfg.unroll:
            for t in range(ticks):              # escape hatch: jaxpr O(ticks)
                carry, _ = tick(carry, jnp.int32(t))
        else:
            carry, _ = jax.lax.scan(tick, carry,
                                    jnp.arange(ticks, dtype=jnp.int32))

        if not has_bwd:
            # caches leave the body as rank-major chunk rows (V*bps, ...) —
            # the same leading layout as the local stage-param stack
            final_caches = jax.tree.map(
                lambda a: a.reshape((V * bps,) + a.shape[2:]),
                carry["caches"])
            return carry["out"], final_caches
        axes_all = (tcfg.pipe_axis,) + tuple(tcfg.data_axes)
        with jax.named_scope(tracing.GRAD_REDUCE):
            loss = jax.lax.psum(carry["loss"], axes_all)
            d_ln = jax.lax.psum(carry["d_ln"], axes_all)
            d_wh = jax.lax.psum(carry["d_wh"], axes_all)
            d_emb = jax.lax.psum(carry["d_emb"], tcfg.pipe_axis)  # rank0 only
            d_stage = jax.tree.map(
                lambda a: jax.lax.psum(a.reshape((V * bps,) + a.shape[2:]),
                                       tuple(tcfg.data_axes)),
                carry["d_stage"])
        return loss, d_stage, d_ln, d_wh, d_emb

    return pipeline_body


def make_terapipe_loss(model: Model, specs, mesh: Mesh, tcfg: TeraPipeConfig,
                       seq_len: int, global_batch: int):
    """Returns loss_fn(params, batch) implementing the pipelined step, plus
    the param sharding tree (NamedShardings) for jit in_shardings.

    Forward-only schedules only (contiguous / interleaved): differentiate
    the returned loss with ``jax.value_and_grad`` as usual.  Explicit-bwd
    schedules compute loss AND grads in one program — use
    :func:`make_terapipe_value_and_grad` (the entry point that serves every
    schedule)."""
    p = _Plan(model, specs, mesh, tcfg, seq_len, global_batch)
    return _make_loss_from_plan(p), p.param_shardings_fn()


def _make_loss_from_plan(p: _Plan):
    """Differentiable loss wrapper over the tick interpreter (fwd-only
    schedules): reassemble the last rank's per-item outputs, run the
    post-pipeline groups + head under plain GSPMD."""
    model, tcfg, mesh = p.model, p.tcfg, p.mesh
    assert not p.assign.has_backward, (
        f"schedule={p.sched!r} computes loss AND grads in one pipelined "
        f"program; build it with make_terapipe_value_and_grad")
    cfg = p.cfg
    K, D, M, l, DM = p.K, p.D, p.M, p.l, p.DM
    data, mb_local, d_model = p.data, p.mb_local, p.d_model
    L, B, slice_lens = p.L, p.B, p.slice_lens
    main, post = p.main, p.post

    pipeline_body = _make_pipeline_body(p)
    out_specs = P(tcfg.pipe_axis, tcfg.data_axes, None, None)
    shmap = jax.shard_map(
        lambda sp, x: pipeline_body(sp, x)[0], mesh=mesh,
        in_specs=(p.stage_in_specs, p.x_spec),
        out_specs=out_specs, check_vma=False)

    @jax.named_scope(tracing.REASSEMBLE)
    def reassemble(out):
        """The last rank's per-item rows of the output ring -> (B, L, d)."""
        rows = DM + 1                         # incl. the idle-tick dump row
        out_last = jax.lax.slice_in_dim(out, (K - 1) * rows,
                                        (K - 1) * rows + DM, axis=0)
        # (D*M, B/D, l, d) -> (B, L, d); batch order is (shard, mb, row).
        # The slice inherits a pipe-sharding on axis 0 that the reshape
        # cannot keep — move it to batch-sharded explicitly first.
        out_last = jax.lax.with_sharding_constraint(
            out_last, NamedSharding(mesh, P(None, tcfg.data_axes, None, None)))
        if p.uniform:
            o = out_last.reshape(D, M, data, mb_local, l, d_model)
            o = jnp.transpose(o, (2, 0, 3, 1, 4, 5))
            x_final = o.reshape(B, L, d_model)
        else:
            # non-uniform: drop each slice's padded tail (static slicing)
            o = out_last.reshape(D, M, data, mb_local, l, d_model)
            segs = [o[:, i, :, :, :slice_lens[i], :] for i in range(M)]
            o = jnp.concatenate(segs, axis=3)         # (D, data, mb, L, d)
            o = jnp.transpose(o, (1, 0, 2, 3, 4))
            x_final = o.reshape(B, L, d_model)
        return jax.lax.with_sharding_constraint(
            x_final, NamedSharding(mesh, P(tcfg.data_axes, None, None)))

    def loss_fn(params, batch):
        x = p.prefix(params, batch)
        stage_params = p.prep_stage_params(params["groups"][main.name])
        x_final = reassemble(shmap(stage_params, x))
        with jax.named_scope(tracing.HEAD_LOSS):
            for g in post:
                x_final = _scan_full(g, params["groups"][g.name], x_final,
                                     cfg.remat)
            return model.head_loss(params, x_final, batch["labels"])

    return loss_fn


def make_terapipe_caches_fn(model: Model, specs, mesh: Mesh,
                            tcfg: TeraPipeConfig, seq_len: int,
                            global_batch: int):
    """Debug/testing: a function (params, batch) -> final per-rank cache
    pytree of the SAME tick loop make_terapipe_loss runs (leaves stacked
    rank-major along axis 0 across the pipe axis, chunk rows V*bps per
    rank).  Used by the idle-tick no-op audits: with
    ``tcfg.extra_ticks`` appended, the result must be bit-identical."""
    p = _Plan(model, specs, mesh, tcfg, seq_len, global_batch)
    assert not p.assign.has_backward, \
        "fwd-only schedules expose the cache carry"
    main = p.main
    pipeline_body = _make_pipeline_body(p)
    cache_struct = jax.eval_shape(
        lambda: p.init_stage_caches((p.V * p.bps,)))
    cache_out_specs = jax.tree.map(
        lambda a: P(*((tcfg.pipe_axis,) + (None,) * (a.ndim - 1))),
        cache_struct)
    shmap = jax.shard_map(
        lambda sp, x: pipeline_body(sp, x)[1], mesh=mesh,
        in_specs=(p.stage_in_specs, p.x_spec),
        out_specs=cache_out_specs, check_vma=False)

    def caches_fn(params, batch):
        x = p.prefix(params, batch)
        return shmap(p.prep_stage_params(params["groups"][main.name]), x)

    return caches_fn


def _make_explicit_value_and_grad(p: _Plan):
    """(params, batch) -> (loss, grads) wrapper for explicit-bwd schedules:
    shard_maps the tick interpreter's loss+grad program, differentiates the
    embed/pre-group prologue with an outer jax.vjp, and maps the rank-major
    stage grads back to layer order."""
    tcfg = p.tcfg
    main = p.main
    tied = p.cfg.tie_embeddings
    assert p.tp == 1, (
        f"schedule={p.sched!r} does not yet support TP inside a stage "
        f"(per-slice head loss and explicit grad psums need tp-aware "
        f"reductions)")
    assert not p.post, "explicit-bwd schedules need the head/loss at the " \
        "last stage; post-pipeline groups are not token-local"
    assert p.cfg.family in ("dense", "moe"), (
        f"schedule={p.sched!r} supports dense/moe families (per-slice LM "
        f"loss at the last stage); got {p.cfg.family}")

    pipeline_body = _make_pipeline_body(p)
    head_in_specs = (P(None), P(None, None))
    labels_spec = P(tcfg.data_axes, None)
    shmap = jax.shard_map(
        lambda sp, hp, x, lab: pipeline_body(sp, x, hp, lab), mesh=p.mesh,
        in_specs=(p.stage_in_specs, head_in_specs, p.x_spec, labels_spec),
        out_specs=(P(), p.stage_in_specs, P(None), P(None, None),
                   P(tcfg.data_axes, None, None)),
        check_vma=False)

    def value_and_grad_fn(params, batch):
        x_emb, prefix_vjp = jax.vjp(lambda prm: p.prefix(prm, batch), params)
        labels = batch["labels"]
        if not p.uniform:
            labels = jnp.pad(labels, ((0, 0), (0, p.l)))
        w_head = params["embed"].T if tied else params["lm_head"]
        head_p = (params["final_ln"], w_head)
        stage_params = p.prep_stage_params(params["groups"][main.name])
        loss, d_stage, d_ln, d_wh, d_emb = shmap(stage_params, head_p,
                                                 x_emb, labels)
        (grads,) = prefix_vjp(d_emb)             # embed (+ pre groups) grads
        grads = dict(grads)
        grads["groups"] = dict(grads["groups"])
        # stage grads come back in the executor's rank-major chunk order:
        # restore layer order, unpad (pad rows are identity blocks: zero
        # grad by construction), merge with the (zero) main-group prefix
        # grads
        grads["groups"][main.name] = jax.tree.map(
            lambda a, d: a + jax.lax.slice_in_dim(
                uninterleave_stacked(d, p.assign), 0, p.n_main, axis=0),
            grads["groups"][main.name], d_stage)
        grads["final_ln"] = grads["final_ln"] + d_ln
        if tied:
            grads["embed"] = grads["embed"] + d_wh.T
        else:
            grads["lm_head"] = grads["lm_head"] + d_wh
        return loss, grads

    return value_and_grad_fn


def make_terapipe_value_and_grad(model: Model, specs, mesh: Mesh,
                                 tcfg: TeraPipeConfig, seq_len: int,
                                 global_batch: int):
    """(params, batch) -> (loss, grads) for ANY registered schedule — the
    one entry point train/dryrun drive.  Fwd-only schedules (contiguous /
    interleaved) wrap the interpreter's loss in ``jax.value_and_grad``
    (autodiff backward, activations live to the drain); explicit-bwd
    schedules (1f1b / interleaved-1f1b / zb-h1) run the SAME interpreter's
    loss+grad program (live activations bounded by the pipeline depth).
    Also returns the param sharding tree builder."""
    p = _Plan(model, specs, mesh, tcfg, seq_len, global_batch)
    if not p.assign.has_backward:
        return (jax.value_and_grad(_make_loss_from_plan(p)),
                p.param_shardings_fn())
    return _make_explicit_value_and_grad(p), p.param_shardings_fn()


def make_gpipe_loss(model: Model, specs, mesh: Mesh, *, n_microbatches: int,
                    pipe_axis="pipe", tp_axis=None, data_axes=("data",),
                    seq_len: int, global_batch: int,
                    cache_dtype: Any = jnp.bfloat16, unroll: bool = False):
    """Microbatch-only pipelining (GPipe, the paper's baseline): D micro-
    batches, a single token slice per sequence.  ``cache_dtype`` /
    ``unroll`` forward into the underlying TeraPipeConfig so the baseline
    is controllable exactly like the TeraPipe executor."""
    tcfg = TeraPipeConfig(n_token_slices=1, n_microbatches=n_microbatches,
                          pipe_axis=pipe_axis, tp_axis=tp_axis,
                          data_axes=tuple(data_axes),
                          cache_dtype=cache_dtype, unroll=unroll)
    return make_terapipe_loss(model, specs, mesh, tcfg, seq_len, global_batch)
