"""HPIPE layer pipeline on a TPU mesh axis.

The FPGA streams activations producer->consumer through per-layer
hardware; stage depth is set by the compiler so throughputs balance. On
a pod mesh the analogue is GPipe-style microbatch pipelining over a
``stage`` mesh axis: each stage owns a contiguous, *cost-balanced* (not
count-balanced — see planner.assign_stages) slice of layers; activations
hop stage->stage with ``ppermute`` (the ICI transfer hides under the
next microbatch's compute); fill/drain bubbles amortize over the
microbatch count exactly like HPIPE's pipeline fills with multiple
partitions in flight.

Implementation: shard_map manual over the stage axis only; data/model
axes stay auto so GSPMD still lays out TP/DP inside each stage.

Two stage-program shapes are supported:

- **homogeneous** (``stack_stages`` + ``pipeline_apply[_gspmd]``): every
  layer has the same signature, stages scan a padded layer stack — the
  LM transformer case.
- **heterogeneous** (``pipeline_apply_hetero[_gspmd]``): each stage runs
  its OWN program with its own activation shapes/dtypes; stage
  boundaries exchange a fixed-width f32 *wire* (``WireFormat``) that
  carries every live value crossing the cut — including residual skip
  edges that span stages — exactly HPIPE's per-layer heterogeneous
  hardware stages. The CNN layer pipeline (models/cnn.stage_programs)
  runs on these. Stage WEIGHTS place the same way the activations do:
  each stage's param slice packs into one row of a ``(S, P)`` byte
  buffer (``ParamFormat``/``PlacedParams``) sharded over the stage
  axis, so a device holds only its own stage's weights — HPIPE's
  per-layer weight memories, not a replicated model.

Scale-out past one pipeline happens on a 2-D ``(data, stage)`` mesh:
once a single layer-pipeline is bubble-free its throughput is fixed by
the bottleneck stage, so the heterogeneous executors take
``n_replicas`` — each data-replica runs the FULL stage pipeline on its
own stage column, the batch shards across replicas, and stage weights
replicate ONLY across the data axis (per-device bytes unchanged from
the 1-replica placed mode). ``pipeline_step_hetero`` exposes one
pipeline tick for continuous batching: a serving loop injects a fresh
microbatch every step instead of draining between requests, so the
fill/drain bubble amortizes over the whole request stream
(``steady_bubble_fraction``), not one batch.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

PyTree = Any


def stack_stages(blocks: PyTree, stage_of: list[int], n_stages: int):
    """Re-pack per-layer stacked params (leading L axis) into per-stage
    stacks (S, Lmax, ...) with a validity mask (S, Lmax). Works under
    jax.eval_shape (static indices only).

    Every stage must own at least one layer: an empty stage would run as
    a silent identity (all-False mask row) and waste a pipeline rung —
    use ``planner.assign_stages`` (which clamps) to build ``stage_of``.
    """
    L = len(stage_of)
    per_stage = [[l for l in range(L) if stage_of[l] == s]
                 for s in range(n_stages)]
    empty = [s for s, g in enumerate(per_stage) if not g]
    if empty:
        raise ValueError(
            f"stage(s) {empty} own no layers ({L} layers over {n_stages} "
            "stages); clamp n_stages to max(stage_of)+1 or rebalance")
    lmax = max(len(g) for g in per_stage)

    def leaf(a):
        out = jnp.zeros((n_stages, lmax) + a.shape[1:], a.dtype)
        for s, g in enumerate(per_stage):
            if g:
                out = out.at[s, :len(g)].set(a[np.array(g)])
        return out

    stacked = jax.tree.map(leaf, blocks)
    mask = np.zeros((n_stages, lmax), bool)
    for s, g in enumerate(per_stage):
        mask[s, :len(g)] = True
    return stacked, jnp.asarray(mask)


def _shard_map_stage(fn: Callable, mesh, in_specs, out_specs,
                     stage_axis, extra_axes: tuple = ()) -> Callable:
    """shard_map over the stage axis (plus any ``extra_axes`` that are
    also manual — the data axis of a 2-D stage x data pipeline);
    remaining mesh axes stay auto/replicated per the specs."""
    manual = frozenset({stage_axis, *extra_axes})
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False,
                         axis_names=manual)


def make_stage_fn(block_fn: Callable) -> Callable:
    """Wrap a per-layer ``block_fn(params_l, x) -> x`` into a stage
    program that scans its (padded) layer stack, skipping invalid pads."""

    def stage_fn(stage_params, mask, x):
        def body(h, xs):
            p, valid = xs
            h2 = block_fn(p, h)
            return jnp.where(valid, h2, h), None

        h, _ = lax.scan(body, x, (stage_params, mask))
        return h

    return stage_fn


def pipeline_apply(stage_fn: Callable, stage_params: PyTree, mask, x_mb,
                   *, mesh, stage_axis: str, n_stages: int,
                   remat: bool = True):
    """Run microbatches through the stage pipeline.

    stage_params: (S, Lmax, ...) pytree sharded P(stage_axis) on axis 0.
    mask: (S, Lmax) bool.
    x_mb: (M, mb, T, d) microbatched activations.
    Returns (M, mb, T, d) outputs (the last stage's results).
    """
    m = x_mb.shape[0]
    fn = stage_fn
    if remat:
        fn = jax.checkpoint(stage_fn, prevent_cse=False)

    def per_device(params_l, mask_l, xs):
        sidx = lax.axis_index(stage_axis)
        p1 = jax.tree.map(lambda a: a[0], params_l)      # drop stage dim
        m1 = mask_l[0]
        act = jnp.zeros_like(xs[0])
        outs = jnp.zeros_like(xs)
        perm = [(s, (s + 1) % n_stages) for s in range(n_stages)]

        def step(carry, i):
            act, outs = carry
            xin = jnp.where(sidx == 0, xs[jnp.clip(i, 0, m - 1)], act)
            y = fn(p1, m1, xin)
            j = i - (n_stages - 1)
            upd = lax.dynamic_update_index_in_dim(
                outs, y, jnp.clip(j, 0, m - 1), 0)
            outs = jnp.where((sidx == n_stages - 1) & (j >= 0), upd, outs)
            act_next = lax.ppermute(y, stage_axis, perm)
            return (act_next, outs), None

        (act, outs), _ = lax.scan(step, (act, outs),
                                  jnp.arange(m + n_stages - 1))
        return outs[None]                                 # add stage dim back

    f = _shard_map_stage(per_device, mesh,
                         (P(stage_axis), P(stage_axis), P()),
                         P(stage_axis), stage_axis)
    outs_all = f(stage_params, mask, x_mb)                # (S, M, mb, T, d)
    return outs_all[-1]                                   # last stage's slice


def microbatch(x, n_microbatches: int, *, pad: bool = False,
               n_replicas: int = 1):
    """(B, ...) -> (M, B/M, ...), or (R, M, B/(R*M), ...) when the
    pipeline is replicated (``n_replicas`` > 1: replica r runs
    microbatches ``x.reshape(R, M, mb)[r]``). Used by every pipeline
    path (homogeneous and heterogeneous), so the contract is shared:

    - a batch not divisible by ``n_replicas * n_microbatches`` raises
      ``ValueError`` naming BOTH divisors (the old message blamed only
      the microbatch count, which sent replicated-serving users hunting
      the wrong knob), unless
    - ``pad=True``: the batch is zero-padded up to the next multiple;
      the caller must drop the trailing ``R*M*mb - B`` padded outputs.
    """
    b = x.shape[0]
    if n_microbatches < 1:
        raise ValueError(f"n_microbatches must be >= 1, got {n_microbatches}")
    if n_replicas < 1:
        raise ValueError(f"n_replicas must be >= 1, got {n_replicas}")
    div = n_microbatches * n_replicas
    if b % div != 0:
        if not pad:
            if n_replicas > 1:
                raise ValueError(
                    f"batch {b} is not divisible by n_replicas "
                    f"{n_replicas} * n_microbatches {n_microbatches} "
                    f"= {div}; pass pad=True to zero-pad (and drop the "
                    "padded outputs) or choose a batch both divide")
            raise ValueError(
                f"batch {b} is not divisible by n_microbatches "
                f"{n_microbatches}; pass pad=True to zero-pad (and drop "
                "the padded outputs) or choose a divisor")
        b2 = -(-b // div) * div
        x = jnp.concatenate(
            [x, jnp.zeros((b2 - b,) + x.shape[1:], x.dtype)], axis=0)
        b = b2
    if n_replicas > 1:
        return x.reshape((n_replicas, n_microbatches, b // div)
                         + x.shape[1:])
    return x.reshape((n_microbatches, b // n_microbatches) + x.shape[1:])


def bubble_fraction(n_microbatches: int, n_stages: int) -> float:
    """Pipeline fill/drain overhead (paper Table I 'Latency: Good')."""
    return (n_stages - 1) / (n_microbatches + n_stages - 1)


def steady_bubble_fraction(n_ticks_injected: int, n_stages: int) -> float:
    """Steady-state bubble of a CONTINUOUS pipeline: one fill of S-1
    ticks amortizes over every microbatch injected across the whole
    request stream, not one batch. With K back-to-back requests of M
    microbatches each, ``n_ticks_injected = K*M`` and the bubble is
    (S-1)/(K*M + S-1) < the single-batch fill bubble (S-1)/(M + S-1)
    for K > 1."""
    return (n_stages - 1) / (n_ticks_injected + n_stages - 1)


def pipeline_apply_gspmd(stage_fn, stage_params, mask, x_mb, *,
                         n_stages: int, stage_axis: str = "pod",
                         mesh=None, data_axis: str = "data",
                         remat: bool = True):
    """Pure-GSPMD pipeline (no shard_map): stages live on a leading axis
    sharded over ``stage_axis``; every step vmaps the stage program over
    that axis (all pods compute in parallel) and ``jnp.roll`` shifts
    activations stage->stage (lowers to collective-permute). Functionally
    identical to pipeline_apply; preferred at production scale where
    mixed manual/auto shard_map stresses the SPMD partitioner.
    """
    m = x_mb.shape[0]
    s = n_stages
    fn = jax.checkpoint(stage_fn, prevent_cse=False) if remat else stage_fn

    def constrain(st):
        if mesh is None:
            return st
        from jax.sharding import PartitionSpec as P
        sizes = dict(mesh.shape)
        spec = [None] * st.ndim
        spec[0] = stage_axis
        if st.shape[1] % sizes.get(data_axis, 1) == 0:
            spec[1] = data_axis
        return jax.lax.with_sharding_constraint(st, P(*spec))

    state = jnp.zeros((s,) + x_mb.shape[1:], x_mb.dtype)
    outs = jnp.zeros_like(x_mb)

    def step(carry, i):
        state, outs = carry
        inject = x_mb[jnp.clip(i, 0, m - 1)]
        state = state.at[0].set(
            jnp.where(i < m, inject, state[0]).astype(state.dtype))
        state = constrain(state)
        y = jax.vmap(fn)(stage_params, mask, state)       # all stages
        y = constrain(y)
        j = i - (s - 1)
        upd = lax.dynamic_update_index_in_dim(outs, y[-1],
                                              jnp.clip(j, 0, m - 1), 0)
        outs = jnp.where(j >= 0, upd, outs)
        state = jnp.roll(y, 1, axis=0)                    # stage s -> s+1
        return (state, outs), None

    (state, outs), _ = lax.scan(step, (state, outs),
                                jnp.arange(m + s - 1))
    return outs


# ---------------------------------------------------------------------------
# heterogeneous stages: wire format + executors
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class WireFormat:
    """Fixed layout of the values crossing one stage boundary.

    Heterogeneous stages produce different activation shapes/dtypes, but
    ppermute/roll need ONE static buffer type on every hop, so each
    boundary flattens its live values into a (mb, width) f32 wire. f32
    is the widening type: bf16 -> f32 -> bf16 round-trips exactly, so
    the pipelined result is bit-identical to sequential execution.

    entries: per value (name, shape, dtype); shape includes the leading
    microbatch dim, which all values must share.
    """
    entries: tuple[tuple[str, tuple, Any], ...]

    @classmethod
    def for_values(cls, entries) -> "WireFormat":
        entries = tuple((n, tuple(s), jnp.dtype(d)) for n, s, d in entries)
        if not entries:
            raise ValueError("a stage boundary must carry at least one value")
        mbs = {s[0] for _, s, _ in entries}
        if len(mbs) != 1:
            raise ValueError(f"mixed microbatch dims across wire: {mbs}")
        return cls(entries)

    @property
    def mb(self) -> int:
        return self.entries[0][1][0]

    def _sizes(self):
        return [int(np.prod(s[1:], dtype=np.int64)) for _, s, _ in self.entries]

    @property
    def width(self) -> int:
        return sum(self._sizes())

    def pack(self, values, width: int) -> jax.Array:
        """values (matching entries order) -> (mb, width) f32 wire."""
        if len(values) != len(self.entries):
            raise ValueError(f"expected {len(self.entries)} values, got "
                             f"{len(values)}")
        flat = [v.astype(jnp.float32).reshape(self.mb, -1) for v in values]
        wire = jnp.concatenate(flat, axis=1) if len(flat) > 1 else flat[0]
        if wire.shape[1] > width:
            raise ValueError(f"wire width {width} < payload {wire.shape[1]}")
        return jnp.pad(wire, ((0, 0), (0, width - wire.shape[1])))

    def unpack(self, wire: jax.Array) -> list[jax.Array]:
        """(mb, >=width) f32 wire -> values in entries order/dtype."""
        out, off = [], 0
        for (name, shape, dtype), size in zip(self.entries, self._sizes()):
            v = lax.slice_in_dim(wire, off, off + size, axis=1)
            out.append(v.reshape(shape).astype(dtype))
            off += size
        return out


class ParamFormat:
    """Fixed BYTE layout of one stage's parameter pytree.

    The per-stage placement analogue of :class:`WireFormat`: stage
    parameter pytrees are heterogeneous (different leaf shapes, dtypes,
    even SparseWeight nodes per stage), but placing each stage's slice
    on only its own devices needs ONE static buffer type that a
    ``(n_stages, width)`` array sharded over the stage axis can carry.
    Leaves are laid out in tree-flatten order as raw uint8, then padded
    to the common stage width. A leaf of itemsize ``k`` and ``n``
    elements is written as ``k`` BYTE PLANES of ``n`` bytes, back to
    back: plane ``j`` holds byte ``j`` (bits ``8j..8j+7``) of every
    element. Itemsize-1 leaves (int8 codes, float8) are one plane.

    Unpack runs inside every tick, so the layout is chosen for it: each
    plane is a 1-D ``u8[n]`` slice, widened to the leaf's unsigned
    integer width, shifted and OR-ed into place, then same-width
    bitcast to the leaf dtype and reshaped. No intermediate has a
    trailing dimension of the itemsize; on the TPU such a dimension
    (``u8[..., 2]`` for bf16) is padded to 128 lanes, so decoding
    through it rewrote ~64x the row's bytes every tick. Bit patterns
    move through integer ops and bitcasts only (never a value cast,
    which would corrupt int32 indices above 2^24 or canonicalize NaN
    payloads), so a stage program running on unpacked params is
    BIT-IDENTICAL to one closing over the originals.

    ``store_dtype`` (core/quant.py) re-stores float leaves narrow
    BEFORE layout: int8 codes and their per-channel f32 scales become
    ordinary leaves of the (quantized) tree, so the same byte planes
    carry them and the roundtrip stays bit-exact on the stored bits.
    Quantization is idempotent, so ``pack`` normalizes its input
    unconditionally — callers may hand it either the original or the
    already-quantized tree.
    """

    def __init__(self, treedef, leaves_meta, store_dtype: str = "native"):
        self.treedef = treedef
        self.leaves_meta = tuple(leaves_meta)   # per leaf: (shape, dtype)
        self.store_dtype = store_dtype

    @classmethod
    def for_tree(cls, tree, store_dtype: str = "native") -> "ParamFormat":
        if store_dtype != "native":
            from repro.core.quant import quantize_tree
            tree = quantize_tree(tree, store_dtype)
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        meta = []
        for l in leaves:
            dt = jnp.dtype(l.dtype)
            if dt == jnp.dtype(bool):
                # bitcast_convert_type has no pred<->u8 lowering; no
                # param tree carries bool leaves, so fail loudly rather
                # than silently value-converting
                raise ValueError(f"unsupported param leaf dtype {dt}")
            meta.append((tuple(l.shape), dt))
        return cls(treedef, meta, store_dtype)

    def _leaf_sizes(self):
        """Per leaf: (element count, itemsize)."""
        return [(int(np.prod(s, dtype=np.int64)), d.itemsize)
                for s, d in self.leaves_meta]

    @property
    def nbytes(self) -> int:
        """Live bytes of this stage's params — the sum of its part
        leaves, NOT the padded buffer width."""
        return sum(n * k for n, k in self._leaf_sizes())

    def pack(self, tree, width: int) -> jax.Array:
        """Param pytree -> (width,) uint8 buffer of byte planes
        (zero-padded). Runs once, on the host."""
        if self.store_dtype != "native":
            from repro.core.quant import quantize_tree
            tree = quantize_tree(tree, self.store_dtype)
        leaves = jax.tree_util.tree_leaves(tree)
        if len(leaves) != len(self.leaves_meta):
            raise ValueError(f"expected {len(self.leaves_meta)} leaves, "
                             f"got {len(leaves)}")
        if self.nbytes > width:
            raise ValueError(f"param width {width} < payload {self.nbytes}")
        buf = np.zeros((width,), np.uint8)
        off = 0
        for l, (shape, dt) in zip(leaves, self.leaves_meta):
            if tuple(l.shape) != shape or jnp.dtype(l.dtype) != dt:
                raise ValueError(f"leaf mismatch: {l.shape}/{l.dtype} vs "
                                 f"{shape}/{dt}")
            # view, never astype: the planes carry the leaf's bits
            word = np.asarray(l).reshape(-1).view(f"<u{dt.itemsize}")
            for j in range(dt.itemsize):
                buf[off:off + word.size] = (word >> (8 * j)).astype(np.uint8)
                off += word.size
        return jnp.asarray(buf)

    def unpack(self, buf: jax.Array):
        """(>= nbytes,) uint8 buffer -> the param pytree, bit-exact."""
        leaves, off = [], 0
        for (shape, dt), (n, k) in zip(self.leaves_meta, self._leaf_sizes()):
            wide = jnp.dtype(f"uint{8 * k}")
            word = lax.slice_in_dim(buf, off, off + n).astype(wide)
            for j in range(1, k):
                plane = lax.slice_in_dim(buf, off + j * n, off + (j + 1) * n)
                # the shift count is a host constant: it lowers with no
                # op_name, so where the tick's lowering merges equal
                # counts across stages, no stage's scope loses an op
                count = np.broadcast_to(wide.type(8 * j), (n,))
                word = word | lax.shift_left(plane.astype(wide), count)
            off += k * n
            leaves.append(lax.bitcast_convert_type(word, dt).reshape(shape))
        return jax.tree_util.tree_unflatten(self.treedef, leaves)


@dataclass(frozen=True)
class PlacedParams:
    """Per-stage parameter placement plan for a heterogeneous pipeline.

    formats[s] packs/unpacks stage s's param subtree; ``width`` is the
    common buffer width (max stage payload) — the per-device parameter
    residency once the (S, width) buffer is sharded over the stage
    axis. ``trees[s]`` holds the concrete per-stage subtrees (keyed by
    fused-node part names) that ``pack()`` serializes.

    The padded ``(S, width)`` form is what a SHARDED buffer must be
    (JAX shards evenly, so every stage row pays the largest stage's
    bytes); ``stage_widths``/``pack_ragged()`` expose the unpadded
    per-stage layout for paths that carry rows individually (the
    single-host packed executor), and ``padding_bytes`` reports what
    the even-width buffer wastes on unbalanced nets.
    """
    formats: tuple
    trees: tuple
    width: int

    @property
    def stage_bytes(self) -> tuple[int, ...]:
        """Live (unpadded) param bytes per stage."""
        return tuple(f.nbytes for f in self.formats)

    @property
    def stage_widths(self) -> tuple[int, ...]:
        """Ragged per-stage buffer widths — exactly each stage's live
        bytes, no padding to the largest stage."""
        return self.stage_bytes

    @property
    def replicated_bytes(self) -> int:
        """Per-device residency of the replicated executor: every
        device holds every stage's params."""
        return sum(self.stage_bytes)

    @property
    def padded_buffer_bytes(self) -> int:
        """Total bytes of the even-width (S, width) buffer."""
        return len(self.formats) * self.width

    @property
    def padding_bytes(self) -> int:
        """Bytes the even-width buffer pads beyond the live payloads —
        what ragged per-stage rows reclaim. Per DEVICE the padding is
        ``width - stage_widths[s]`` on stage s's devices; summed over
        stages it is this number."""
        return self.padded_buffer_bytes - sum(self.stage_widths)

    def pack(self) -> jax.Array:
        """(n_stages, width) uint8 buffer — row s is stage s's params.
        Shard axis 0 over the stage axis (``jax.device_put`` with
        ``launch/shardings.stage_param_shardings``) and each device
        holds ONLY its stage's weights."""
        return jnp.stack([f.pack(t, self.width)
                          for f, t in zip(self.formats, self.trees)])

    def pack_ragged(self) -> tuple:
        """Per-stage ``(stage_widths[s],)`` uint8 buffers — the same
        payloads as :meth:`pack` rows without the even-width padding.
        The heterogeneous executors accept this tuple as
        ``stage_params`` on the single-host (mesh-less) path, where the
        one device would otherwise hold the whole padded buffer; a
        SHARDED placement still needs the even ``(S, width)`` form
        (JAX cannot shard rows of unequal width over a mesh axis)."""
        return tuple(f.pack(t, f.nbytes)
                     for f, t in zip(self.formats, self.trees))


def _check_hetero_params(stage_fns, n_stages, stage_params, mesh,
                         stage_axis):
    """Shared validation for the heterogeneous executors. Returns
    ``(placed, ragged)``: ``ragged`` marks the tuple-of-rows form from
    :meth:`PlacedParams.pack_ragged` (single-host packed params, no
    even-width padding)."""
    if len(stage_fns) != n_stages:
        raise ValueError(f"{len(stage_fns)} stage programs for "
                         f"{n_stages} stages")
    placed = stage_params is not None
    ragged = placed and isinstance(stage_params, (tuple, list))
    if ragged:
        if len(stage_params) != n_stages:
            raise ValueError(f"{len(stage_params)} ragged param rows for "
                             f"{n_stages} stages")
        if mesh is not None and stage_axis in mesh.shape:
            raise ValueError(
                "ragged per-stage param rows have unequal widths and "
                "cannot shard over the stage axis; pass the even "
                "(S, width) buffer from PlacedParams.pack() for "
                "placement on a mesh, or drop the mesh for the "
                "single-host packed path")
    elif placed and (mesh is None or stage_axis not in mesh.shape):
        have = "no mesh" if mesh is None else \
            f"mesh axes {tuple(mesh.shape)}"
        raise ValueError(
            "per-stage weight placement (stage_params=...) requires a "
            f"mesh with a {stage_axis!r} axis to place each stage's "
            f"weights onto, got {have}; pass mesh=launch.mesh.make_mesh"
            f"(({n_stages},), ({stage_axis!r},)), drop stage_params "
            "to run with replicated params, or pass "
            "PlacedParams.pack_ragged() rows for single-host packed "
            "params")
    return placed, ragged


def _run_hetero_stages(stage_fns, state, stage_params, *, replicated):
    """Run every stage program on its own state slot. ``state`` is
    (S, mb, W), or (S, R, mb, W) with ``replicated`` — each replica
    slot gets its OWN trace of the stage program (no vmap), so the
    per-sample computation graph is identical to the 1-replica path
    and replicated output is bitwise-equal to single-replica output.
    Each stage's ops carry the named scope ``stage<k>``, so a profile
    can tell the stages apart (metadata only: the program is unchanged)."""
    placed = stage_params is not None

    def one(k, st_k):
        fn = stage_fns[k]
        args = (stage_params[k],) if placed else ()
        with jax.named_scope(f"stage{k}"):
            if replicated:
                return jnp.stack([fn(*args, st_k[r])
                                  for r in range(st_k.shape[0])])
            return fn(*args, st_k)

    return jnp.stack([one(k, state[k]) for k in range(len(stage_fns))])


def pipeline_apply_hetero(stage_fns: list, x_wire, *, mesh,
                          stage_axis: str, n_stages: int,
                          stage_params=None, n_replicas: int = 1,
                          data_axis: str = "data"):
    """shard_map layer pipeline over HETEROGENEOUS per-stage programs.

    stage_fns[s]: (mb, W) f32 wire -> (mb, W) f32 wire — stage s's whole
    program (unpack live-in values, run its IR slice, pack live-out).
    x_wire: (M, mb, W) packed input microbatches. Returns the last
    stage's (M, mb, W) wires.

    Params come in two flavours:

    - ``stage_params=None`` — each stage program closes over its
      parameters, which therefore replicate across the stage axis.
    - ``stage_params`` = the ``(S, P)`` uint8 buffer from
      :meth:`PlacedParams.pack` — per-stage weight PLACEMENT: the
      buffer is sharded ``P(stage_axis)``, so each device holds only
      its own stage's packed weights, and every ``lax.switch`` branch
      receives the device-local row (``stage_fns[s]`` then takes
      ``(param_buf, wire)`` and unpacks its own layout).

    Every device runs ``lax.switch`` over the stage programs — the SPMD
    program is shared, the selected branch differs per stage index, and
    activations (including residual skips captured in the wire) hop
    stage->stage with ppermute exactly as in ``pipeline_apply``.

    2-D scale-out (``n_replicas`` > 1): the mesh carries a
    ``(data_axis, stage_axis)`` grid, ``x_wire`` grows a leading
    replica dim (R, M, mb, W) sharded over ``data_axis`` (use
    ``microbatch(..., n_replicas=R)``), and every data-replica runs the
    FULL stage pipeline on its own stage column — ppermute hops stay
    within each replica. The placed buffer keeps its ``P(stage_axis)``
    spec, so stage weights replicate ONLY across the data axis:
    per-device bytes are unchanged from the 1-replica placed mode.
    Returns (R, M, mb, W).
    """
    placed, ragged = _check_hetero_params(stage_fns, n_stages,
                                          stage_params, mesh, stage_axis)
    if ragged:
        raise ValueError(
            "the shard_map executor threads the placed buffer through "
            "lax.switch as one (S, width) array; ragged rows only run "
            "on the gspmd single-host path")
    rep = n_replicas > 1
    if rep:
        if x_wire.shape[0] != n_replicas:
            raise ValueError(
                f"x_wire leading dim {x_wire.shape[0]} != n_replicas "
                f"{n_replicas}; build it with microbatch(x, M, "
                "n_replicas=R)")
        if mesh is None or mesh.shape.get(data_axis) != n_replicas:
            have = "no mesh" if mesh is None else \
                f"mesh axes {dict(mesh.shape)}"
            raise ValueError(
                f"n_replicas={n_replicas} needs a mesh with a "
                f"{data_axis!r} axis of that size (one stage column "
                f"per replica), got {have}")
    m = x_wire.shape[1] if rep else x_wire.shape[0]

    def per_device(*args):
        if placed:
            pbuf, xs = args
            p1 = pbuf[0]                      # drop stage dim: own row only
        else:
            (xs,) = args
        if rep:
            xs = xs[0]                        # drop local replica dim
        sidx = lax.axis_index(stage_axis)
        act = jnp.zeros_like(xs[0])
        outs = jnp.zeros_like(xs)
        perm = [(s, (s + 1) % n_stages) for s in range(n_stages)]

        def step(carry, i):
            act, outs = carry
            xin = jnp.where(sidx == 0, xs[jnp.clip(i, 0, m - 1)], act)
            if placed:
                y = lax.switch(sidx, stage_fns, p1, xin)
            else:
                y = lax.switch(sidx, stage_fns, xin)
            j = i - (n_stages - 1)
            upd = lax.dynamic_update_index_in_dim(
                outs, y, jnp.clip(j, 0, m - 1), 0)
            outs = jnp.where((sidx == n_stages - 1) & (j >= 0), upd, outs)
            act_next = lax.ppermute(y, stage_axis, perm)
            return (act_next, outs), None

        (act, outs), _ = lax.scan(step, (act, outs),
                                  jnp.arange(m + n_stages - 1))
        if rep:
            return outs[None, None]           # add (replica, stage) dims
        return outs[None]                     # add stage dim back

    if rep:
        x_spec = P(data_axis)
        out_spec = P(data_axis, stage_axis)
        extra = (data_axis,)
    else:
        x_spec = P()
        out_spec = P(stage_axis)
        extra = ()
    if placed:
        f = _shard_map_stage(per_device, mesh, (P(stage_axis), x_spec),
                             out_spec, stage_axis, extra)
        outs_all = f(stage_params, x_wire)    # ([R,] S, M, mb, W)
    else:
        f = _shard_map_stage(per_device, mesh, (x_spec,), out_spec,
                             stage_axis, extra)
        outs_all = f(x_wire)                  # ([R,] S, M, mb, W)
    if rep:
        return outs_all[:, -1]                # (R, M, mb, W)
    return outs_all[-1]                       # last stage's slice


def _hetero_constrainers(mesh, stage_axis, data_axis, rep):
    """(state_constrain, out_constrain) for the gspmd executors: state
    leads with (S[, R], ...) — stage then replica — and outputs lead
    with ([R,] M, ...). No-ops for axes the mesh doesn't carry."""
    def state_c(st):
        if mesh is None:
            return st
        spec = [None] * st.ndim
        if stage_axis in mesh.shape:
            spec[0] = stage_axis
        if rep and data_axis in mesh.shape:
            spec[1] = data_axis
        if not any(spec):
            return st
        return jax.lax.with_sharding_constraint(st, P(*spec))

    def out_c(o):
        if not rep or mesh is None or data_axis not in mesh.shape:
            return o
        return jax.lax.with_sharding_constraint(
            o, P(data_axis, *([None] * (o.ndim - 1))))

    return state_c, out_c


def pipeline_apply_gspmd_hetero(stage_fns: list, x_wire, *, n_stages: int,
                                stage_axis: str = "pod", mesh=None,
                                stage_params=None, n_replicas: int = 1,
                                data_axis: str = "data"):
    """Pure-GSPMD heterogeneous pipeline (no shard_map).

    The wire state lives on a leading (S, mb, W) axis; each scan step
    runs every stage's program on its own slot (on a sharded mesh each
    program's operands live on one stage shard, so GSPMD places them
    there) and ``jnp.roll`` shifts wires stage->stage. Works unsharded
    too (mesh=None): correct single-device semantics for tests/serving,
    at S-fold step cost. Functionally identical to
    ``pipeline_apply_hetero``.

    ``stage_params``: optional per-stage weight payloads —

    - the ``(S, P)`` uint8 buffer from :meth:`PlacedParams.pack`:
      per-stage weight PLACEMENT. Shard it ``P(stage_axis)``
      (``jax.device_put`` with
      ``launch/shardings.stage_param_shardings``) so stage k's row
      lives only on stage k's devices; ``stage_fns[k]`` then takes
      ``(param_buf, wire)``. Placement REQUIRES a mesh carrying
      ``stage_axis``: with ``mesh=None`` there are no stage devices to
      place onto — the buffer would silently replicate, defeating the
      point — so that combination raises.
    - the tuple of ragged rows from :meth:`PlacedParams.pack_ragged`:
      single-host PACKED params — each row is exactly its stage's live
      bytes, so the one device pays no even-width padding. Valid only
      WITHOUT a stage axis to place onto (unequal widths cannot shard);
      a mesh carrying ``stage_axis`` raises.

    2-D scale-out (``n_replicas`` > 1): ``x_wire`` grows a leading
    replica dim (R, M, mb, W) (``microbatch(..., n_replicas=R)``), the
    state becomes (S, R, mb, W) constrained ``P(stage_axis,
    data_axis)`` on a ``(data, stage)`` mesh, and each replica slot
    runs its own trace of every stage program — batch sharded across
    replicas, placed rows replicated only across the data axis.
    Returns (R, M, mb, W). Mesh-less replication is bitwise-identical
    to the 1-replica path; on a 2-D MESH the GSPMD partitioner may
    re-layout ops (~1e-10 logit drift observed on XLA:CPU) — when
    replication must be bit-reproducible, use the shard_map executor
    (``pipeline_apply_hetero``), whose per-device program is literally
    the single-pipeline program.
    """
    placed, ragged = _check_hetero_params(stage_fns, n_stages,
                                          stage_params, mesh, stage_axis)
    rep = n_replicas > 1
    if rep and x_wire.shape[0] != n_replicas:
        raise ValueError(
            f"x_wire leading dim {x_wire.shape[0]} != n_replicas "
            f"{n_replicas}; build it with microbatch(x, M, n_replicas=R)")
    m = x_wire.shape[1] if rep else x_wire.shape[0]
    s = n_stages
    state_c, out_c = _hetero_constrainers(mesh, stage_axis, data_axis, rep)

    if placed and not ragged:
        stage_params = jax.lax.with_sharding_constraint(
            stage_params, P(stage_axis, None)) \
            if mesh is not None and stage_axis in mesh.shape else stage_params
    mb_shape = x_wire.shape[2:] if rep else x_wire.shape[1:]
    lead = (s, n_replicas) if rep else (s,)
    state = jnp.zeros(lead + mb_shape, x_wire.dtype)
    outs = jnp.zeros_like(x_wire)

    def step(carry, i):
        state, outs = carry
        inject = x_wire[:, jnp.clip(i, 0, m - 1)] if rep else \
            x_wire[jnp.clip(i, 0, m - 1)]
        state = state.at[0].set(
            jnp.where(i < m, inject, state[0]).astype(state.dtype))
        state = state_c(state)
        ys = _run_hetero_stages(stage_fns, state, stage_params,
                                replicated=rep)
        ys = state_c(ys)
        j = i - (s - 1)
        upd = lax.dynamic_update_index_in_dim(
            outs, ys[-1], jnp.clip(j, 0, m - 1), 1 if rep else 0)
        outs = jnp.where(j >= 0, upd, outs)
        outs = out_c(outs)
        state = jnp.roll(ys, 1, axis=0)                   # stage s -> s+1
        return (state, outs), None

    (state, outs), _ = lax.scan(step, (state, outs),
                                jnp.arange(m + s - 1))
    return outs


def concat_hetero_outputs(out_wires, unpack_out, n_microbatches: int,
                          n_replicas: int = 1):
    """Reassemble a hetero executor's output wires into one batch:
    unpack each microbatch wire and concatenate replica-major —
    ``microbatch(..., n_replicas=R)``'s C-order reshape means replica
    r owns the contiguous batch slice r*B/R:(r+1)*B/R, so this restores
    the original sample order. Shared by serve/dryrun so the ordering
    rule lives in one place."""
    if n_replicas > 1:
        mbs = [unpack_out(out_wires[r][i]) for r in range(n_replicas)
               for i in range(n_microbatches)]
    else:
        mbs = [unpack_out(out_wires[i]) for i in range(n_microbatches)]
    return jnp.concatenate(mbs, axis=0)


def pipeline_step_hetero(stage_fns: list, state, in_wire, *,
                         n_stages: int, stage_axis: str = "stage",
                         mesh=None, stage_params=None,
                         n_replicas: int = 1, data_axis: str = "data"):
    """ONE pipeline tick — the continuous-batching primitive.

    Instead of scanning a whole batch through fill+drain
    (``pipeline_apply_gspmd_hetero``), a serving loop holds the
    pipeline state across calls and ticks it once per microbatch:
    inject ``in_wire`` at stage 0, run every stage on its current slot,
    emit stage S-1's output (the microbatch injected S-1 ticks
    earlier), shift. Back-to-back requests keep injecting — the
    pipeline NEVER drains between them, so the fill bubble amortizes
    over the whole request stream (``steady_bubble_fraction``).

    state: (S, mb, W) wires, or (S, R, mb, W) with ``n_replicas`` > 1
    (zeros before the first tick; the caller threads it through —
    ``jax.jit(..., donate_argnums=(0,))`` reuses the buffer so the
    steady-state loop allocates nothing). in_wire: (mb, W) / (R, mb, W)
    — zeros when the queue is empty (an idle slot, not a hazard: slots
    never mix). Same param flavours and mesh rules as the batch
    executor. Returns ``(next_state, out_wire)``.
    """
    placed, ragged = _check_hetero_params(stage_fns, n_stages,
                                          stage_params, mesh, stage_axis)
    rep = n_replicas > 1
    want = (n_stages, n_replicas) if rep else (n_stages,)
    if state.shape[:len(want)] != want:
        raise ValueError(f"state leading dims {state.shape[:len(want)]} "
                         f"!= (n_stages{', n_replicas' if rep else ''}) "
                         f"= {want}")
    state_c, out_c = _hetero_constrainers(mesh, stage_axis, data_axis, rep)
    state = state.at[0].set(in_wire.astype(state.dtype))
    state = state_c(state)
    ys = _run_hetero_stages(stage_fns, state, stage_params, replicated=rep)
    ys = state_c(ys)
    return jnp.roll(ys, 1, axis=0), out_c(ys[-1])
