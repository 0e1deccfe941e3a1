"""Every registered Pallas kernel compiles for TPU v5e at the mnist cell's shapes.

Nothing runs: the kernels are compiled with ``interpret=False`` for a
described ``v5e:2x2`` topology, so the TPU compiler refuses here what the
interpreter would accept (misaligned blocks, layouts Mosaic cannot lower,
over-budget VMEM).  The topology is described inside a fixture, never at
import: only one process at a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.fft_repulsion import MAX_N_BOXES, P_ORDER
from repro.kernels import ops

N = 70_000          # mnist: 70,000 x 784
K = 90              # 3 * perplexity 30
W = 409             # symmetric ELL width of the mnist cell's exact K = 91 graph
# (turns, rows a turn, width) of each degree bucket of the cells' graphs
# (similarity.degree_buckets at the default 512-row block)
MNIST_BUCKETS = [(1, 96, 409), (2, 592, 328), (5, 680, 264), (7, 936, 216),
                 (9, 1128, 176), (10, 1312, 144), (14, 1736, 120),
                 (6, 1896, 96)]
DIGITS_N, DIGITS_W = 1797, 189
DIGITS_BUCKETS = [(1, 176, 189), (1, 248, 152), (1, 568, 128), (1, 808, 104)]
DIM = 784
BLOCK_Q, BLOCK_DB = 512, 2048    # the exact KNN's distance tile
# lattice nodes per dimension at the default 48 boxes and at the cap
NODES = [n_boxes * (P_ORDER - 1) + 1 for n_boxes in (48, MAX_N_BOXES)]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return make


def _buckets(s, n, shapes):
    """Shapes of a graph's degree buckets."""
    from repro.core.similarity import DegreeBuckets

    i32 = jnp.int32
    return DegreeBuckets(
        rows=tuple(s((t, r), i32) for t, r, _ in shapes),
        cols=tuple(s((t, r, w), i32) for t, r, w in shapes),
        vals=tuple(s((t, r, w)) for t, r, w in shapes),
        inv=s((n,), i32))


def _gathers_outside_loops(text):
    """Output sizes of the compiled module's gathers that no while body
    reaches."""
    import math
    import re

    lines, calls, bodies, comp = {}, {}, set(), None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.-]+) .*\{$", line)
        if head:
            comp = head.group(1)
            lines[comp], calls[comp] = [], set()
        elif comp is not None:
            lines[comp].append(line)
            calls[comp].update(re.findall(
                r"(?:calls|to_apply|condition|body)=%([\w.-]+)", line))
            bodies.update(re.findall(r"body=%([\w.-]+)", line))
    inside, todo = set(), list(bodies)
    while todo:
        c = todo.pop()
        if c not in inside:
            inside.add(c)
            todo.extend(calls.get(c, ()))
    return [math.prod(int(d) for d in m.group(1).split(",") if d)
            for comp, body in lines.items() if comp not in inside
            for line in body
            if (m := re.search(r"= \w+\[([\d,]*)\]\S* gather\(", line))]


def _cases(s):
    """kernel name -> list of (entry-point kwargs, argument shapes)."""
    i32 = jnp.int32
    return {
        "morton_encode": [({}, (s((N, 2)), s((2,)), s(())))],
        "pairwise_sq_dists": [({}, (s((BLOCK_Q, DIM)), s((BLOCK_DB, DIM))))],
        "attractive_ell": [({}, (s((N, 2)), s((N, W), i32), s((N, W))))],
        "bsp_search": [({}, (s((N, K)), s(())))],
        "fft_spread": [
            (dict(nodes=nodes),
             (s((N, 2), i32), s((N, P_ORDER)), s((N, P_ORDER)), s((N, 3))))
            for nodes in NODES
        ],
        "fft_gather": [
            ({}, (s((nodes, nodes, 4)), s((N, 2), i32), s((N, P_ORDER)),
                  s((N, P_ORDER))))
            for nodes in NODES
        ],
    }


KERNELS = ["morton_encode", "pairwise_sq_dists", "attractive_ell",
           "bsp_search", "fft_spread", "fft_gather"]


def test_every_registered_kernel_is_covered():
    assert set(KERNELS) == set(ops.kernel_registry())


@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_for_v5e(name, spec):
    fn = ops.get_kernel(name, "pallas")
    for kwargs, args in _cases(spec)[name]:
        compiled = fn.lower(*args, interpret=False, **kwargs).compile()
        assert "tpu_custom_call" in compiled.as_text(), (name, kwargs)


def test_descent_step_layers_survive_the_v5e_compile(spec):
    """The step's layer scopes reach the v5e-compiled module: each of its
    top-level loops carries the layer it runs in, where a device profile
    reads it back."""
    import re

    from repro.api.backends import make_backend
    from repro.core import scopes
    from repro.core.tsne import NeighborGraph, TsneConfig, TsneState, tsne_step

    n, w, i32 = DIGITS_N, DIGITS_W, jnp.int32          # the digits cell
    state = TsneState(y=spec((n, 2)), velocity=spec((n, 2)),
                      gains=spec((n, 2)), iteration=spec((), i32))
    graph = NeighborGraph(
        p_cols=spec((n, w), i32), p_vals=spec((n, w)), p_logp=spec(()), n=n,
        buckets=_buckets(spec, n, DIGITS_BUCKETS))
    text = tsne_step.lower(
        state, graph, spec(()), spec(()),
        backend=make_backend("barnes_hut", TsneConfig(), n), lr=149.75,
        min_gain=0.01,
    ).compile().as_text()
    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    loops = {}
    for line in entry.splitlines():
        if " while(" in line:
            path = re.search(r'op_name="([^"]*)"', line).group(1).split("/")
            loops[line.split()[0]] = [p for p in path if p in scopes.STEP_SCOPES]
    # the tree build's searchsorted, the walk, and the attractive buckets'
    # turns (a bucket of one turn runs as no loop)
    layers = sorted(v[-1] for v in loops.values())
    assert [v for v in layers if v != scopes.ATTRACTIVE] == \
        [scopes.BH_TRAVERSAL, scopes.BH_TREE]
    for name in (scopes.BH_SUMMARIZE, scopes.UPDATE):
        assert f"/{name}/" in entry, name


def test_bucketed_attractive_gathers_a_turn_at_a_time(spec):
    """At the mnist cell's bucket shapes every gather larger than one turn's
    runs inside a loop: none is left for the v5e compiler to unroll at the
    size of the whole graph."""
    from repro.core.attractive import attractive_forces_bucketed

    text = jax.jit(attractive_forces_bucketed).lower(
        spec((N, 2)), _buckets(spec, N, MNIST_BUCKETS)).compile().as_text()
    turn = max(r * w for _, r, w in MNIST_BUCKETS)
    outside = _gathers_outside_loops(text)
    assert outside and max(outside) <= turn, (outside, turn)
    assert text.count(" while(") >= sum(t > 1 for t, _, _ in MNIST_BUCKETS)


def test_bh_walk_gathers_one_node_row_per_turn(spec):
    """Each turn of the compiled walk reads a node's record with one gather,
    not one gather per field."""
    import re

    from repro.core.quadtree import LinearQuadtree
    from repro.core.repulsive import bh_repulsion_sorted
    from repro.core.summarize import TreeSummary

    cap, i32 = 2 * N + 1, jnp.int32
    tree = LinearQuadtree(start=spec((cap,), i32), end=spec((cap,), i32),
                          level=spec((cap,), i32), skip=spec((cap,), i32),
                          n_nodes=spec((), i32), depth=16)
    summ = TreeSummary(count=spec((cap,)), sum_y=spec((cap, 2)),
                       com=spec((cap, 2)), side=spec((cap,)))
    text = bh_repulsion_sorted.lower(spec((N, 2)), tree, summ, spec(())) \
        .compile().as_text()
    in_body = [line for line in text.splitlines() if " gather(" in line
               and "while/body/" in re.search(r'op_name="([^"]*)"', line).group(1)]
    assert len(in_body) == 1, in_body
    assert f"f32[{N},8]" in in_body[0]


def test_fft_step_interpolates_by_matmul(spec):
    """The FFT step at the mnist-fft cell's shapes spreads and gathers by
    matmul: no scatter or gather of the N * 9 taps, which the v5e compiler
    unrolls into code that grows with N."""
    import re

    from repro.api.backends import make_backend
    from repro.core import scopes
    from repro.core.tsne import NeighborGraph, TsneConfig, TsneState, tsne_step

    i32 = jnp.int32
    state = TsneState(y=spec((N, 2)), velocity=spec((N, 2)),
                      gains=spec((N, 2)), iteration=spec((), i32))
    graph = NeighborGraph(
        p_cols=spec((N, W), i32), p_vals=spec((N, W)), p_logp=spec(()), n=N,
        buckets=_buckets(spec, N, MNIST_BUCKETS))
    cfg = TsneConfig(method="fft", fft_n_boxes=50)
    text = tsne_step.lower(
        state, graph, spec(()), spec(()),
        backend=make_backend("fft", cfg, N), lr=5833.33, min_gain=0.01,
    ).compile().as_text()
    interp = (scopes.FFT_SPREAD, scopes.FFT_GATHER)
    ops = [(m.group(1), line) for line in text.splitlines()
           if (m := re.search(r' (scatter|gather|dot|convolution)\(', line))
           and any(f"/{s}/" in line for s in interp)]
    assert not [line for op, line in ops if op in ("scatter", "gather")]
    assert {op for op, _ in ops} & {"dot", "convolution"}
