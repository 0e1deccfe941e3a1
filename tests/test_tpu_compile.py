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
W = 397             # symmetric ELL width of the mnist cell's exact KNN graph
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
