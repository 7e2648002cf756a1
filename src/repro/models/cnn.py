"""The paper's own networks: ResNet-50 V1, MobileNet-V1, MobileNet-V2.

Every (non-depthwise) sparse convolution runs through the fused
implicit-GEMM block-sparse conv (repro/kernels/sparse_conv.py) — the
HPIPE convolution unit — which gathers surviving weight blocks against
the UNEXPANDED NHWC activation; no im2col patch tensor is ever
materialized (see DESIGN.md §3). Dense convolutions use the native
conv; depthwise convolutions stay dense (the paper's depthwise unit is
separate and the MobileNets are evaluated dense).

Conv weights are stored 2D as (k*k*cin, cout) with rows in HWIO order
(row f = (ky*k + kx)*cin + c), so the block ids of a pruned weight
decompose into the fused kernel's (ky, kx, channel-block) gathers.

Each model's layer list is a flat ``ConvSpec`` sequence that
``repro/core/graph.LayerGraph`` resolves into the layer-graph IR
(explicit residual edges, fused-relu flags). ``cnn_forward`` is a
single graph interpreter over that IR — the per-model if/elif
monoliths are gone (the old ResNet body survives only as
``cnn_forward_reference``, the bit-for-bit regression oracle in
tests). The interpreter, the stage planner and ``stage_programs`` all
run the FUSED graph by default (core/fusion.py): dw->pw pairs,
residual ``add``(+relu) tails and the avgpool->fc head collapse into
super-nodes whose intermediates live only in VMEM (DESIGN.md §5).
``stage_programs`` compiles the IR into per-stage wire programs for
the heterogeneous layer pipeline (core/pipeline.py), with residual
edges that cross a stage cut carried in the wire's skip buffer
(DESIGN.md §4).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.fusion import conv_part, fused_graph_for
from repro.core.graph import INPUT, ConvSpec, LayerGraph, graph_for
from repro.models import layers as L
from repro.models.layers import SparseWeight


# ---------------------------------------------------------------------------
# layer spec builders (the "TensorFlow graph" the compiler walks)
# ---------------------------------------------------------------------------

def resnet50_specs() -> list[ConvSpec]:
    specs = [ConvSpec("conv1", "conv", 3, 64, 7, 2, 224),
             ConvSpec("pool1", "maxpool", 64, 64, 3, 2, 112)]
    blocks = [(3, 64, 256, 56), (4, 128, 512, 28),
              (6, 256, 1024, 14), (3, 512, 2048, 7)]
    cin = 64
    for si, (n, mid, out, hw) in enumerate(blocks):
        for bi in range(n):
            stride = 2 if (bi == 0 and si > 0) else 1
            ihw = hw * stride      # input spatial before downsample
            pre = f"s{si}b{bi}"
            block_in = specs[-1].name
            specs += [
                ConvSpec(f"{pre}_c1", "conv", cin, mid, 1, stride, ihw),
                ConvSpec(f"{pre}_c2", "conv", mid, mid, 3, 1, hw),
                ConvSpec(f"{pre}_c3", "conv", mid, out, 1, 1, hw,
                         relu=False),
            ]
            resid = block_in
            if bi == 0:
                resid = f"{pre}_proj"
                specs.append(ConvSpec(f"{pre}_proj", "conv", cin, out, 1,
                                      stride, ihw, relu=False,
                                      input_from=block_in))
            specs.append(ConvSpec(f"{pre}_add", "add", out, out, 1, 1, hw,
                                  residual_from=resid,
                                  input_from=f"{pre}_c3"))
            cin = out
    specs += [ConvSpec("avgpool", "avgpool", 2048, 2048, 7, 1, 7),
              ConvSpec("fc", "fc", 2048, 1000, 1, 1, 1)]
    return specs


_MBV1 = [(32, 64, 1), (64, 128, 2), (128, 128, 1), (128, 256, 2),
         (256, 256, 1), (256, 512, 2)] + [(512, 512, 1)] * 5 + \
        [(512, 1024, 2), (1024, 1024, 1)]


def mobilenet_v1_specs() -> list[ConvSpec]:
    specs = [ConvSpec("conv1", "conv", 3, 32, 3, 2, 224)]
    hw = 112
    for i, (cin, cout, s) in enumerate(_MBV1):
        specs += [ConvSpec(f"b{i}_dw", "dw", cin, cin, 3, s, hw),
                  ConvSpec(f"b{i}_pw", "conv", cin, cout, 1, 1, hw // s)]
        hw //= s
    specs += [ConvSpec("avgpool", "avgpool", 1024, 1024, 7, 1, 7),
              ConvSpec("fc", "fc", 1024, 1000, 1, 1, 1)]
    return specs


_MBV2 = [  # (expansion, cout, n, stride)
    (1, 16, 1, 1), (6, 24, 2, 2), (6, 32, 3, 2), (6, 64, 4, 2),
    (6, 96, 3, 1), (6, 160, 3, 2), (6, 320, 1, 1)]


def mobilenet_v2_specs() -> list[ConvSpec]:
    specs = [ConvSpec("conv1", "conv", 3, 32, 3, 2, 224)]
    cin, hw = 32, 112
    for si, (t, cout, n, stride) in enumerate(_MBV2):
        for bi in range(n):
            s = stride if bi == 0 else 1
            mid = cin * t
            pre = f"s{si}b{bi}"
            block_in = specs[-1].name
            if t != 1:
                specs.append(ConvSpec(f"{pre}_exp", "conv", cin, mid, 1, 1, hw))
            specs += [ConvSpec(f"{pre}_dw", "dw", mid, mid, 3, s, hw),
                      ConvSpec(f"{pre}_pj", "conv", mid, cout, 1, 1, hw // s,
                               relu=False)]
            if s == 1 and cin == cout:
                # MobileNet-V2 linear bottleneck: residual add, NO relu
                specs.append(ConvSpec(f"{pre}_add", "add", cout, cout, 1, 1,
                                      hw // s, residual_from=block_in,
                                      relu=False))
            hw //= s
            cin = cout
    specs += [ConvSpec("conv_last", "conv", 320, 1280, 1, 1, 7),
              ConvSpec("avgpool", "avgpool", 1280, 1280, 7, 1, 7),
              ConvSpec("fc", "fc", 1280, 1000, 1, 1, 1)]
    return specs


def specs_for(name: str) -> list[ConvSpec]:
    return {"resnet50": resnet50_specs,
            "mobilenet_v1": mobilenet_v1_specs,
            "mobilenet_v2": mobilenet_v2_specs}[name]()


# ---------------------------------------------------------------------------
# params + node executors
# ---------------------------------------------------------------------------

def _maybe_sparse(w2d, sp, cin: Optional[int] = None):
    """Prune a 2D weight block-balanced. For conv weights pass ``cin``:
    the block-row size must divide the input-channel count (not just
    k*k*cin) so every block is a single (ky, kx, channel-block) gather
    of the fused implicit-GEMM kernel."""
    if sp is None or not sp.enabled:
        return w2d
    d_in, d_out = w2d.shape
    unit = cin if cin is not None else d_in
    bm = sp.block_m if unit % sp.block_m == 0 else _largest_div(unit, sp.block_m)
    bn = sp.block_n if d_out % sp.block_n == 0 else _largest_div(d_out, sp.block_n)
    if bm < 4 or bn < 4 or d_in // bm < 4:
        return w2d                       # too small to prune blockwise
    import dataclasses
    from repro.core import sparsity as S
    return S.to_block_balanced(
        w2d, dataclasses.replace(sp, block_m=bm, block_n=bn))


def _largest_div(n, cap):
    for b in range(min(cap, n), 0, -1):
        if n % b == 0:
            return b
    return 1


def init_cnn(cfg, key, *, image_size: int = 224):
    specs = [s for s in specs_for(cfg.name) if s.kind in ("conv", "dw", "fc")]
    params = {}
    keys = jax.random.split(key, len(specs))
    sp = cfg.sparsity
    for s, k in zip(specs, keys):
        if s.kind == "conv":
            w = L.dense_init(k, (s.k * s.k * s.cin, s.cout),
                             s.k * s.k * s.cin, jnp.bfloat16)
            params[s.name] = {"w": _maybe_sparse(w, sp, cin=s.cin),
                              "b": jnp.zeros((s.cout,), jnp.bfloat16)}
        elif s.kind == "dw":
            params[s.name] = {
                "w": L.dense_init(k, (s.k, s.k, s.cin), s.k * s.k, jnp.bfloat16),
                "b": jnp.zeros((s.cin,), jnp.bfloat16)}
        elif s.kind == "fc":
            # the classifier prunes with the rest of the network (the
            # paper's 85% covers it; the planner already prices a
            # SparseWeight fc via op_cost_from_sparse) — also the
            # largest single dense residue, which matters once
            # per-stage placement bounds a stage's weight bytes
            w = L.dense_init(k, (s.cin, s.cout), s.cin, jnp.bfloat16)
            params[s.name] = {"w": _maybe_sparse(w, sp),
                              "b": jnp.zeros((s.cout,), jnp.bfloat16)}
    return params


def conv2d(x, p, s: ConvSpec, *, relu=True, residual=None):
    """The HPIPE convolution unit: fused implicit-GEMM sparse conv for
    pruned weights (patches form in VMEM per grid step, never in HBM),
    native conv for dense weights. No im2col tensor either way.
    ``residual``: optional fused skip tensor added in the epilogue
    before the activation (graph fusion, core/fusion.py). An int8
    SparseWeight flows into the kernel dispatcher (which owns the
    fast-path-vs-dequant choice); a dense QuantizedWeight dequantizes
    at stage entry — the native conv has no epilogue to factor the
    scale into."""
    from repro.core.quant import QuantizedWeight
    w = p["w"]
    if isinstance(w, SparseWeight):
        from repro.kernels import ops as kops
        return kops.sparse_conv(x, w, p["b"], k=s.k, stride=s.stride,
                                relu=relu, residual=residual)
    if isinstance(w, QuantizedWeight):
        w = w.dequant()
    w4 = w.reshape(s.k, s.k, s.cin, s.cout)              # HWIO row order
    # f32 accumulation (what the MXU does natively with bf16 inputs);
    # XLA:CPU would otherwise accumulate the conv in bf16
    y = lax.conv_general_dilated(
        x, w4, (s.stride, s.stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.float32)
    y = y + p["b"].astype(jnp.float32)
    if residual is not None:
        # fused epilogue in the activation dtype — the exact op sequence
        # the unfused graph ran (conv -> round -> add -> relu), so fused
        # == unfused BITWISE on the dense path and the elementwise chain
        # stays bit-stable across compilation contexts (shard_map vs
        # standalone)
        y = y.astype(x.dtype) + residual
        return jax.nn.relu(y) if relu else y
    if relu:
        y = jax.nn.relu(y)
    return y.astype(x.dtype)


def depthwise(x, p, s: ConvSpec, *, relu=True):
    from repro.core.quant import QuantizedWeight
    from repro.kernels import ops as kops
    w = p["w"]
    if isinstance(w, QuantizedWeight):
        w = w.dequant()      # VPU MAC chains: no epilogue for the scale
    y = kops.depthwise_conv(x, w, stride=s.stride)
    y = y + p["b"]
    return jax.nn.relu(y) if relu else y


def _fused_dw_pw(x, params, node: ConvSpec, residual=None):
    """Execute a fused dw_pw super-node: the depthwise intermediate
    lives only in VMEM (kernels/dw_pw_fused.py). A SPARSE pointwise
    weight falls back to the two-op sequence inside the node (the
    fusion legality note in DESIGN.md §5: the fused MXU matmul needs a
    dense (C, Cout) operand; the paper evaluates the MobileNets dense,
    so this is the off-spec path)."""
    dw_s, pw_s = node.parts[0], node.parts[1]
    dw_p, pw_p = params[dw_s.name], params[pw_s.name]
    if isinstance(pw_p["w"], SparseWeight):
        y = depthwise(x, dw_p, dw_s, relu=dw_s.relu)
        return conv2d(y, pw_p, pw_s, relu=node.relu, residual=residual)
    from repro.kernels import ops as kops
    return kops.dw_pw_conv(x, dw_p["w"], dw_p["b"], pw_p["w"], pw_p["b"],
                           stride=node.stride, dw_relu=dw_s.relu,
                           relu=node.relu, residual=residual)


def fc_apply(p, x):
    """The classifier matmul, dense or pruned — f32 inputs and
    accumulation either way, so logits stay f32. Shared by the graph
    interpreter AND ``cnn_forward_reference`` (one dispatch point, so
    the bit-for-bit oracle bar keeps guarding the graph machinery, not
    the weight format)."""
    from repro.core.quant import QuantizedWeight
    from repro.kernels import ops as kops
    w = p["w"]
    x32 = x.astype(jnp.float32)
    if isinstance(w, SparseWeight):
        y = kops.sparse_matmul(x32, w)
    elif isinstance(w, QuantizedWeight):
        if kops._INT8_FAST:
            # int8 matmul, f32 accumulate, per-channel scale on the
            # accumulator — same factoring as the sparse kernels
            y = (x32 @ w.codes.astype(jnp.float32)) * w.scale
        else:
            y = x32 @ w.dequant().astype(jnp.float32)
    else:
        y = x32 @ w.astype(jnp.float32)
    return y + p["b"].astype(jnp.float32)


def run_node(node: ConvSpec, params, *args):
    """Execute one IR node (original layer kinds + the fused
    super-nodes emitted by core/fusion.py). ``args`` are the resolved
    input values (primary[, residual] — see LayerGraph.inputs)."""
    x = args[0]
    res = args[1] if (node.residual_from and node.kind != "add") else None
    if node.kind == "conv":
        p = params[conv_part(node).name]
        y = conv2d(x, p, node, relu=node.relu, residual=res)
        if node.pool_k:
            # fused pooling epilogue (core/fusion.py R4): same op the
            # standalone maxpool node runs, applied in-node so the
            # pre-pool tensor never crosses a node/stage boundary
            y = lax.reduce_window(y, -jnp.inf, lax.max,
                                  (1, node.pool_k, node.pool_k, 1),
                                  (1, node.pool_stride, node.pool_stride, 1),
                                  "SAME")
        return y
    if node.kind == "dw_pw":
        return _fused_dw_pw(x, params, node, residual=res)
    if node.kind == "dw":
        return depthwise(x, params[node.name], node, relu=node.relu)
    if node.kind == "maxpool":
        return lax.reduce_window(x, -jnp.inf, lax.max,
                                 (1, node.k, node.k, 1),
                                 (1, node.stride, node.stride, 1), "SAME")
    if node.kind == "avgpool":
        return x.mean(axis=(1, 2))                       # global avg pool
    if node.kind == "add":
        y = x + args[1]
        return jax.nn.relu(y) if node.relu else y
    if node.kind in ("fc", "avgpool_fc"):
        if node.kind == "avgpool_fc":                    # fused head
            x = x.mean(axis=(1, 2))
        return fc_apply(params[conv_part(node).name], x)
    raise ValueError(f"unknown node kind {node.kind!r}")


# ---------------------------------------------------------------------------
# the graph interpreter (replaces the per-model forward monoliths)
# ---------------------------------------------------------------------------

def _interpret(g: LayerGraph, params, x, *, start=0, stop=None,
               env=None) -> dict:
    """Execute nodes [start, stop) of ``g``. ``env`` maps value names to
    arrays and must contain every value the slice reads; returns the
    env extended with each executed node's output. Dead values are NOT
    freed here — slicing callers (stage programs) bound liveness via
    the wire contract instead. Each node's ops carry the named scope of
    its name, so a profile tells fused nodes apart."""
    env = dict(env or {})
    if x is not None:
        env[INPUT] = x
    stop = len(g.nodes) if stop is None else stop
    for i in range(start, stop):
        node = g.nodes[i]
        args = [env[src] for src in g.inputs[i]]
        with jax.named_scope(node.name):
            env[node.name] = run_node(node, params, *args)
    return env


def cnn_forward(cfg, params, images, *, graph: Optional[LayerGraph] = None):
    """images: (N, H, W, 3) -> logits (N, 1000). Executes the layer-graph
    IR node-by-node — one interpreter for all three CNNs. Runs the
    FUSED graph by default (core/fusion.py: dw->pw, residual epilogues
    and the avgpool->fc head collapse into super-nodes whose
    intermediates never touch HBM); pass ``graph=graph_for(name)`` for
    the unfused view."""
    g = graph if graph is not None else fused_graph_for(cfg.name)
    env = _interpret(g, params, images.astype(jnp.bfloat16))
    return env[g.output]


# ---------------------------------------------------------------------------
# heterogeneous stage programs for the layer pipeline
# ---------------------------------------------------------------------------

def node_shapes(cfg, params, image_shape,
                graph: Optional[LayerGraph] = None) -> dict:
    """ShapeDtypeStruct for every IR value (INPUT + each node output) at
    a concrete image shape — the shape inference the stage partitioner
    needs to size wires. Defaults to the fused graph (matching
    ``cnn_forward``); pass an explicit graph for the unfused view."""
    g = graph if graph is not None else fused_graph_for(cfg.name)

    def all_outputs(imgs):
        return _interpret(g, params, imgs.astype(jnp.bfloat16))

    imgs = jax.ShapeDtypeStruct(tuple(image_shape), jnp.float32)
    return jax.eval_shape(all_outputs, imgs)


def stage_part_names(g: LayerGraph, stage_of) -> list[list[str]]:
    """Per stage: the fused-node PART names owning parameters — the
    keys of the param dict each stage's weights live under (a fused
    super-node's params stay keyed by its original part specs)."""
    slices = g.partition(list(stage_of))
    out = []
    for sl in slices:
        names = []
        for node in g.nodes[sl.start:sl.stop]:
            for part in (node.parts or (node,)):
                if part.kind in ("conv", "dw", "fc"):
                    names.append(part.name)
        out.append(names)
    return out


def stage_param_trees(g: LayerGraph, stage_of, params) -> list[dict]:
    """Extract each stage's parameter slice from the full pytree —
    exactly the part params its IR slice reads, nothing else. This is
    what per-stage placement materializes on a stage's devices."""
    return [{n: params[n] for n in names}
            for names in stage_part_names(g, stage_of)]


def stage_programs(cfg, params, stage_of, image_shape, *,
                   graph: Optional[LayerGraph] = None,
                   placed: bool = False, quantize: str = "native"):
    """Compile the IR into per-stage wire programs.

    stage_of: stage id per IR node of the FUSED graph (contiguous, from
    ``planner.plan`` — fused super-nodes are atomic, so a
    stage cut can never land inside a fusion). image_shape: (mb, H, W, 3)
    of ONE microbatch. Returns ``(stage_fns, pack_in, unpack_out, width)``:

    - stage_fns[s]: (mb, width) f32 wire -> (mb, width) f32 wire. The
      wire carries the stage boundary's live values (activations AND
      residual skips crossing the cut), each value f32-widened
      (bf16 -> f32 is exact, so pipelined == sequential bit-for-bit).
    - pack_in(images): (mb, H, W, 3) -> input wire for stage 0.
    - unpack_out(wire): last stage's wire -> logits.

    ``placed=True`` compiles PLACED stage programs instead: each
    stage_fns[s] takes ``(param_buf, wire)`` and unpacks its own param
    slice from the device-local row of the placement buffer
    (``pipeline.ParamFormat`` — bit-exact, so placed == replicated
    BITWISE), and a fifth return value ``pipeline.PlacedParams`` plans
    the buffer: ``.pack()`` builds the (S, P) uint8 array to
    ``jax.device_put`` with ``launch/shardings.stage_param_shardings``.
    No stage program closes over a weight, so nothing replicates.

    ``quantize`` (core/quant.py store dtype) re-stores the weights ONCE
    up front, so the placed trees, their ParamFormats, and the
    non-placed closures all read the SAME quantized pytree — placed ==
    non-placed stays bitwise even under int8.
    """
    from repro.core import pipeline as pp
    g = graph if graph is not None else fused_graph_for(cfg.name)
    if quantize != "native":
        from repro.core.quant import quantize_tree
        params = quantize_tree(params, quantize)
    slices = g.partition(list(stage_of))
    shapes = node_shapes(cfg, params, image_shape, graph=g)

    def fmt(names):
        return pp.WireFormat.for_values(
            [(n, shapes[n].shape, shapes[n].dtype) for n in names])

    in_fmts = [fmt(sl.in_live) for sl in slices]
    out_fmts = [fmt(sl.out_live) for sl in slices]
    width = max(f.width for f in in_fmts + out_fmts)

    placed_params = None
    if placed:
        trees = stage_param_trees(g, stage_of, params)
        pfmts = [pp.ParamFormat.for_tree(t, store_dtype=quantize)
                 for t in trees]
        pwidth = max(max((f.nbytes for f in pfmts), default=0), 1)
        placed_params = pp.PlacedParams(formats=tuple(pfmts),
                                        trees=tuple(trees), width=pwidth)

    # named scopes (metadata only) split a profile of a stage into its
    # weight decode (``params``), wire codecs and nodes
    def make_stage(sl, in_fmt, out_fmt, pfmt=None):
        def run(sparams, wire):
            with jax.named_scope("wire_in"):
                env = dict(zip(sl.in_live, in_fmt.unpack(wire)))
            env = _interpret(g, sparams, None, start=sl.start, stop=sl.stop,
                             env=env)
            with jax.named_scope("wire_out"):
                return out_fmt.pack([env[n] for n in sl.out_live], width)

        def stage(wire):
            return run(params, wire)

        def stage_placed(pbuf, wire):
            with jax.named_scope("params"):
                sparams = pfmt.unpack(pbuf)
            return run(sparams, wire)

        return stage_placed if pfmt is not None else stage

    if placed:
        stage_fns = [make_stage(sl, fi, fo, pf)
                     for sl, fi, fo, pf in zip(slices, in_fmts, out_fmts,
                                               placed_params.formats)]
    else:
        stage_fns = [make_stage(sl, fi, fo)
                     for sl, fi, fo in zip(slices, in_fmts, out_fmts)]

    def pack_in(images):
        with jax.named_scope("pack_in"):
            return in_fmts[0].pack([images.astype(jnp.bfloat16)], width)

    def unpack_out(wire):
        with jax.named_scope("unpack_out"):
            return out_fmts[-1].unpack(wire)[0]

    if placed:
        return stage_fns, pack_in, unpack_out, width, placed_params
    return stage_fns, pack_in, unpack_out, width


# ---------------------------------------------------------------------------
# frozen pre-IR reference (regression oracle: tests compare the graph
# interpreter and the pipelined executors against this bit-for-bit)
# ---------------------------------------------------------------------------

def cnn_forward_reference(cfg, params, images):
    """The original per-model forward monoliths, kept verbatim as the
    exact-equivalence bar for the IR refactor. Do not extend."""
    name = cfg.name
    specs = {s.name: s for s in specs_for(name)}
    x = images.astype(jnp.bfloat16)
    if name == "resnet50":
        x = conv2d(x, params["conv1"], specs["conv1"])
        x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "SAME")
        blocks = [(3, 64, 256), (4, 128, 512), (6, 256, 1024), (3, 512, 2048)]
        for si, (nb, mid, out) in enumerate(blocks):
            for bi in range(nb):
                pre = f"s{si}b{bi}"
                resid = x
                y = conv2d(x, params[f"{pre}_c1"], specs[f"{pre}_c1"])
                y = conv2d(y, params[f"{pre}_c2"], specs[f"{pre}_c2"])
                y = conv2d(y, params[f"{pre}_c3"], specs[f"{pre}_c3"], relu=False)
                if bi == 0:
                    resid = conv2d(x, params[f"{pre}_proj"],
                                   specs[f"{pre}_proj"], relu=False)
                x = jax.nn.relu(y + resid)
        x = x.mean(axis=(1, 2))
    elif name == "mobilenet_v1":
        x = conv2d(x, params["conv1"], specs["conv1"])
        for i in range(len(_MBV1)):
            x = depthwise(x, params[f"b{i}_dw"], specs[f"b{i}_dw"])
            x = conv2d(x, params[f"b{i}_pw"], specs[f"b{i}_pw"])
        x = x.mean(axis=(1, 2))
    elif name == "mobilenet_v2":
        x = conv2d(x, params["conv1"], specs["conv1"])
        cin = 32
        for si, (t, cout, n, stride) in enumerate(_MBV2):
            for bi in range(n):
                pre = f"s{si}b{bi}"
                resid = x
                y = x
                if t != 1:
                    y = conv2d(y, params[f"{pre}_exp"], specs[f"{pre}_exp"])
                y = depthwise(y, params[f"{pre}_dw"], specs[f"{pre}_dw"])
                y = conv2d(y, params[f"{pre}_pj"], specs[f"{pre}_pj"], relu=False)
                s = stride if bi == 0 else 1
                if s == 1 and cin == cout:
                    y = y + resid
                x = y
                cin = cout
        x = conv2d(x, params["conv_last"], specs["conv_last"])
        x = x.mean(axis=(1, 2))
    else:
        raise ValueError(name)
    # fc_apply is the one (deliberate) shared dispatch with the
    # interpreter: the classifier weight may be pruned, and both sides
    # must execute the identical matmul for the bit-for-bit bar to
    # isolate the graph machinery
    return fc_apply(params["fc"], x)
