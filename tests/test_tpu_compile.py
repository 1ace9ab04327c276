"""The actor kernels compile for a TPU v5e at real widths.

Nothing runs: each test lowers a kernel with ``interpret=False`` for one
chip of a described ``v5e:2x2`` topology and checks that the compiled
program holds the Mosaic kernel (``tpu_custom_call``), so what the chip's
compiler would refuse (tiling, VMEM, layout) fails here first. Shapes are
the paper's: a replay minibatch of B=64 graphs, M=14 devices, O=N*L=10
options; layer 1 maps 7 device / 4 option features to 128, layer 2 maps
128 to 64, the edge MLP has H=E=64. M=256 is a deployment-scale network.
``cells`` > 0 vmaps the kernel over that many per-cell weight sets, as
the sweep pack and the population do.

All v5e compile tests live in this one file: the topology is described
in a fixture, so only the worker that runs these tests loads the TPU
compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.edge_score import edge_score
from repro.kernels.gcn_agg import gcn_agg

B, O = 64, 10
DEV_F, OPT_F = 7, 4          # device / option node features
H1, H2, E = 128, 64, 64      # GCN widths and edge-MLP hidden


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compiled_text(fn, shapes, sharding, cells=0):
    if cells:
        fn = jax.vmap(fn)
        shapes = [(cells, *s) for s in shapes]
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("m,cells", [(14, 0), (256, 0), (14, 4)])
@pytest.mark.parametrize("layer,side", [(1, "dev"), (1, "opt"), (2, "dev"),
                                        (2, "opt")])
def test_gcn_agg_compiles_for_v5e(one_chip, m, cells, layer, side):
    n_self, n_nbr = (m, O) if side == "dev" else (O, m)
    if layer == 1:
        f_self, f_nbr = (DEV_F, OPT_F) if side == "dev" else (OPT_F, DEV_F)
        h = H1
    else:
        f_self = f_nbr = H1
        h = H2
    shapes = [(B, n_self, n_nbr), (B, n_self, f_self), (B, n_nbr, f_nbr),
              (f_self, h), (f_nbr, h), (h,)]
    text = _compiled_text(lambda *a: gcn_agg(*a, interpret=False), shapes,
                          one_chip, cells)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("m,cells", [(14, 0), (256, 0), (14, 4)])
def test_edge_score_compiles_for_v5e(one_chip, m, cells):
    shapes = [(B, m, H2), (B, O, H2), (B, m, O), (H2, E), (E,), (H2, E),
              (E,), (E,), (1,)]
    text = _compiled_text(lambda *a: edge_score(*a, interpret=False), shapes,
                          one_chip, cells)
    assert "tpu_custom_call" in text
