"""Per-layer profiler: layer chain -> weighted profile Graph.

Capability parity with the reference's profiling stack (SURVEY.md §5.1), which
needs THREE hook mechanisms plus a C++ autograd patch:
* torchsummary forward hooks for shapes/params (torchsummary.py:30-105),
* torchprofiler forward monkey-patches + cuda.synchronize and backward
  pre/post hooks — requiring the pre_hook.patch PyTorch rebuild (D1) —
  for per-layer fwd/bwd times (profiling.py:104-168),
* torchgraph TensorWrapper propagation for dataflow (graph_creator.py:55-195).

On TPU none of that machinery exists or is needed:
* shapes/params come from init_model's shape chain (the model IS a chain),
* per-layer times come from jitting each layer's forward and forward+backward
  separately and timing to jax.block_until_ready ("time" mode) — accepting
  that XLA fusion makes per-layer attribution approximate (documented
  deviation, SURVEY.md §7 "hard parts"), or from XLA
  HLO cost analysis divided by peak FLOP/s ("flops" mode: deterministic,
  device-free, used in tests),
* dataflow is the layer chain itself; jaxpr capture is available via
  jax.make_jaxpr for diagnostics.

Output is a Graph in the reference-compatible text format (graph/graph.py), and
``profile_and_partition`` chains straight into the hierarchical optimizer —
replacing the reference's profile -> bash-parsing -> optimizer -> codegen
4-phase pipeline (run_template.sh:396-565) with two function calls.
"""

from __future__ import annotations

import statistics
import time
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ddlbench_tpu.config import HardwareModel
from ddlbench_tpu.graph.graph import Graph, Node
from ddlbench_tpu.models.layers import LayerModel, init_model, param_bytes


def _time_callable(fn, *args, repeats: int = 5, warmup: int = 2) -> float:
    """Median wall-time of fn(*args) in ms, each execution timed to
    jax.block_until_ready of its output."""
    for _ in range(max(1, warmup)):
        jax.block_until_ready(fn(*args))
    samples = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        samples.append((time.perf_counter() - t0) * 1000.0)
    return statistics.median(samples)


def _flops_of(fn, *args) -> float:
    """FLOP estimate from XLA's cost analysis of the compiled fn."""
    compiled = jax.jit(fn).lower(*args).compile()
    return float(compiled.cost_analysis().get("flops", 0.0))


def _profile_node(layer, state, params, xs, how, mode, hw, repeats):
    """The shared per-node measurement core of profile_model/profile_dag:
    (fwd_ms, bwd_ms) for one layer, its inputs pre-combined with ``how``
    ("" / "concat" / "add") as the node's own cost. One home so the timing
    protocol, the bwd = 2x fwd FLOPs heuristic, and token handling cannot
    drift between the chain and DAG profilers."""
    from ddlbench_tpu.models.branchy import _combine

    def fwd(p, *xin, _layer=layer, _s=state, _how=how):
        return _layer.apply(p, _s, _combine(list(xin), _how), True)[0]

    def fwd_bwd(p, *xin, _fwd=fwd):
        def scalar(p, *xin):
            return jnp.sum(_fwd(p, *xin).astype(jnp.float32))

        # token ids are not differentiable — only dL/dw for that layer
        args = ((0,) if jnp.issubdtype(xin[0].dtype, jnp.integer)
                else tuple(range(1 + len(xin))))
        return jax.grad(scalar, argnums=args)(p, *xin)

    if mode == "time":
        f_ms = _time_callable(jax.jit(fwd), params, *xs, repeats=repeats)
        fb_ms = _time_callable(jax.jit(fwd_bwd), params, *xs, repeats=repeats)
        return f_ms, max(fb_ms - f_ms, 0.0)
    if mode == "flops":
        f_flops = _flops_of(fwd, params, *xs)
        b_flops = 2.0 * f_flops  # dL/dw + dL/dx each cost ~one forward
        return 1000.0 * f_flops / hw.peak_flops, 1000.0 * b_flops / hw.peak_flops
    raise ValueError(f"unknown profile mode {mode!r}")


def profile_model(
    model: LayerModel,
    batch_size: int,
    mode: str = "time",
    dtype=jnp.float32,
    hw: Optional[HardwareModel] = None,
    repeats: int = 5,
    seed: int = 0,
    input_time_ms: float = 0.0,
) -> Graph:
    """Profile every layer; returns a chain Graph with per-node
    forward/backward times (ms), activation sizes and parameter sizes (bytes).

    ``input_time_ms`` > 0 prepends a synthetic "input" source node carrying
    the measured per-batch data-loading cost (reference parity:
    profiler/image_classification/main.py:388-407 appends an Input node so
    the partitioner prices host-side loading into stage 0). Layer node ids
    stay the layer indices; the input node id is "input".
    """
    hw = hw or HardwareModel()
    params_list, state_list, shapes = init_model(model, jax.random.key(seed))
    itemsize = jnp.dtype(dtype).itemsize
    nodes = []
    key = jax.random.key(seed + 1)
    for idx, layer in enumerate(model.layers):
        in_shape, out_shape = shapes[idx], shapes[idx + 1]
        if any(isinstance(n, tuple) for n in (*in_shape, *out_shape)):
            raise ValueError(
                f"profile_model: {model.name}'s layer {layer.name!r} takes "
                f"or hands on more than one array a boundary "
                f"({in_shape} -> {out_shape}); a node of the profiled graph "
                f"carries one array")
        p, s = params_list[idx], state_list[idx]
        key, sub = jax.random.split(key)
        if idx == 0 and model.input_kind == "tokens":
            # the first layer (embedding) takes int32 ids in [0, vocab);
            # activations downstream are floats as usual
            x = jax.random.randint(
                sub, (batch_size, *in_shape), 0, model.num_classes, jnp.int32
            )
        else:
            x = jax.random.normal(sub, (batch_size, *in_shape), dtype)

        f_ms, b_ms = _profile_node(layer, s, p, [x], "", mode, hw, repeats)
        act_bytes = float(batch_size) * _prod(out_shape) * itemsize
        nodes.append(
            Node(
                node_id=str(idx),
                node_desc=layer.name,
                forward_compute_time=f_ms,
                backward_compute_time=b_ms,
                activation_size=act_bytes,
                parameter_size=float(param_bytes(p)),
            )
        )
    if input_time_ms > 0.0:
        in_bytes = float(batch_size) * _prod(shapes[0]) * itemsize
        nodes.insert(0, Node(
            node_id="input",
            node_desc="Input",
            forward_compute_time=float(input_time_ms),
            backward_compute_time=0.0,
            activation_size=in_bytes,
            parameter_size=0.0,
        ))
    return Graph.chain(nodes)


def measure_input_ms(data, batches: int = 3) -> float:
    """Average wall-clock cost of fetching one training batch from a data
    source with the SyntheticData/OnDiskData ``batch`` interface (host read +
    device upload + normalize). The profiler's Input-node weight for the -s
    on-disk path. Callers should pass a throwaway data instance: sequential
    on-disk streams advance with every fetch."""
    import time as _time

    # warm: page cache, jit of the normalize step
    jax.block_until_ready(data.batch(0, 0))
    t0 = _time.perf_counter()
    for i in range(batches):
        out = data.batch(0, i)
    jax.block_until_ready(out)
    return 1000.0 * (_time.perf_counter() - t0) / batches


def fold_input_node(graph: Graph) -> Graph:
    """Collapse the synthetic Input source node into its successor: the
    partitioner prices data loading into the stage hosting layer 0 (a chip
    cannot run "just data loading", so Input must never form its own stage).
    Returns a new chain graph of the layer nodes; graphs without an input
    node pass through unchanged."""
    order = graph.topological_sort()
    if not order or order[0].node_id != "input":
        return graph
    import dataclasses

    rest = [dataclasses.replace(n) for n in order[1:]]
    rest[0].forward_compute_time += order[0].forward_compute_time
    return Graph.chain(rest)


def _prod(shape: Sequence[int]) -> float:
    out = 1.0
    for d in shape:
        out *= d
    return out


def profile_dag(
    model,
    batch_size: int,
    mode: str = "time",
    dtype=jnp.float32,
    hw: Optional[HardwareModel] = None,
    repeats: int = 5,
    seed: int = 0,
    return_shapes: bool = False,
) -> Graph:
    """Profile a DagModel (models/branchy.py) node by node; returns the REAL
    branchy Graph — node ids are layer indices, edges are the declared
    dataflow. The native analog of the reference's TensorWrapper tracer
    (graph_creator.py:55-195), which is how its branchy profiles
    (resnext50_generated.txt, the inception family) come to exist. Each
    node's cost includes its input-combine (concat/add) op. With
    ``return_shapes`` also returns the per-node output shapes (so callers
    like the auto-partition path can build to_packed_chain without
    re-initializing the model)."""
    from ddlbench_tpu.models.branchy import init_dag

    hw = hw or HardwareModel()
    params_list, state_list, out_shapes = init_dag(
        model, jax.random.key(seed))
    itemsize = jnp.dtype(dtype).itemsize
    g = Graph()
    key = jax.random.key(seed + 1)
    nodes = []
    for idx, layer in enumerate(model.layers):
        preds = model.inputs[idx]
        in_shapes = [model.in_shape if p < 0 else out_shapes[p]
                     for p in preds]
        p, s = params_list[idx], state_list[idx]
        xs = []
        for sh in in_shapes:
            key, sub = jax.random.split(key)
            if idx == 0 and model.input_kind == "tokens":
                xs.append(jax.random.randint(
                    sub, (batch_size, *sh), 0, model.num_classes, jnp.int32))
            else:
                xs.append(jax.random.normal(sub, (batch_size, *sh), dtype))

        f_ms, b_ms = _profile_node(layer, s, p, xs, model.combine[idx],
                                   mode, hw, repeats)
        nodes.append(Node(
            node_id=str(idx),
            node_desc=layer.name,
            forward_compute_time=f_ms,
            backward_compute_time=b_ms,
            activation_size=float(batch_size) * _prod(out_shapes[idx])
            * itemsize,
            parameter_size=float(param_bytes(p)),
        ))
    for n in nodes:
        g.add_node(n)
    for idx in range(len(model.layers)):
        for pr in model.inputs[idx]:
            if pr >= 0:
                g.add_edge(str(pr), str(idx))
    if return_shapes:
        return g, [tuple(s) for s in out_shapes]
    return g


def coarse_chain(graph: Graph, model) -> Graph:
    """Aggregate a DAG profile into the chain of its articulation blocks
    (models/branchy.block_spans): summed compute/params per block, boundary
    activation = the single tensor crossing each cut. Its node index k IS
    layer k of branchy.to_chain(model), so stage bounds transfer 1:1.
    Library/reporting view: the auto-partition path uses the finer
    packed_chain_graph below instead (cuts anywhere, packed boundaries);
    this is the profile view matching the default (to_chain) execution
    form that non-auto runs use."""
    from ddlbench_tpu.models.branchy import block_spans

    spans = block_spans(model)
    chain_nodes = []
    for k, (a, b) in enumerate(spans):
        nd = Node(str(k), node_desc=f"block{k}")
        for i in range(a, b):
            n = graph.nodes[str(i)]
            nd.forward_compute_time += n.forward_compute_time
            nd.backward_compute_time += n.backward_compute_time
            nd.parameter_size += n.parameter_size
        if b < len(model.layers):
            # the cut at b crosses exactly one source (articulation
            # property): its output is the boundary tensor
            (src,) = {s for d in range(b, len(model.layers))
                      for s in model.inputs[d] if 0 <= s < b}
            nd.activation_size = graph.nodes[str(src)].activation_size
        else:
            nd.activation_size = graph.nodes[str(b - 1)].activation_size
        chain_nodes.append(nd)
    return Graph.chain(chain_nodes)


def packed_chain_graph(graph: Graph, model, batch_size: int,
                       itemsize: int = 4) -> Graph:
    """Node-granular chainized view of a DAG profile for topo-prefix cuts.

    Node i keeps its measured cost/params; its activation_size becomes the
    PACKED bytes crossing the cut after it — the sum of every tensor (incl.
    the model input, when consumed later) flowing from [0, i] to [i+1, n).
    A cut at any position is then executable via branchy.to_packed_chain
    (one flat boundary buffer per cut), so the partitioner prices and the
    runtime executes the same boundaries — the reference's multi-tensor
    stage edges (StageRuntime, runtime.py:193-223), TPU-form. The chain
    shape also keeps the native C++ DP applicable."""
    from ddlbench_tpu.models.branchy import crossing_ids

    n = len(model.layers)
    in_bytes = float(batch_size) * _prod(model.in_shape) * itemsize
    chain_nodes = []
    for i in range(n):
        src = graph.nodes[str(i)]
        nd = Node(str(i), node_desc=src.node_desc,
                  forward_compute_time=src.forward_compute_time,
                  backward_compute_time=src.backward_compute_time,
                  parameter_size=src.parameter_size)
        if i < n - 1:
            nd.activation_size = sum(
                in_bytes if pid < 0
                else graph.nodes[str(pid)].activation_size
                for pid in crossing_ids(model, i + 1))
        else:
            nd.activation_size = src.activation_size
        chain_nodes.append(nd)
    return Graph.chain(chain_nodes)


def chunk_cost_ms(graph: Graph, bounds: Sequence[int]):
    """Per-chunk (forward_ms, backward_ms) sums of a profile graph over
    chosen stage/chunk bounds — the raw material for cost-weighted
    timetables (partition/schedule.quantize_cost_vectors): chunk c owns
    graph nodes [bounds[c], bounds[c+1]) in topological order, exactly
    the spans the partitioner chose and the pipeline runtime executes."""
    order = graph.topological_sort()
    f_ms, b_ms = [], []
    for c in range(len(bounds) - 1):
        span = order[bounds[c]:bounds[c + 1]]
        f_ms.append(sum(n.forward_compute_time for n in span))
        b_ms.append(sum(n.backward_compute_time for n in span))
    return f_ms, b_ms


def profile_and_partition(
    model: LayerModel,
    batch_size: int,
    num_chips: int,
    num_hosts: int = 1,
    mode: str = "time",
    hw: Optional[HardwareModel] = None,
):
    """profile -> hierarchical partition; returns (graph, PartitionResult).

    One-call replacement for the reference's 4-phase PipeDream pipeline
    (profiler main.py -> optimizer_graph_hierarchical.py -> bash stdout
    parsing -> convert_graph_to_model.py)."""
    from ddlbench_tpu.partition.optimizer import (
        partition_hierarchical,
        stamp_stage_ids,
    )

    hw = hw or HardwareModel()
    graph = profile_model(model, batch_size, mode=mode, hw=hw)
    result = partition_hierarchical(graph, num_chips, hw, num_hosts=num_hosts)
    stamp_stage_ids(graph, result)
    return graph, result
