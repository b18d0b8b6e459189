"""Ahead-of-time compiles of the served path's Pallas kernels for a
described TPU v5e, at the paper's real sizes: the KNN index is the
paper world's 14,886-row training split (E = 128).

Nothing runs: each test lowers the kernel with ``interpret=False`` and
compiles it for a chip that is described, not attached, which is where
Mosaic refuses what the interpreter accepts (unlowerable primitives,
misaligned slices, more VMEM than a kernel may use). The topology is
described inside a fixture — never at import — and the persistent
compilation cache is off around these compiles: an entry written for a
described chip cannot be read back without one.
"""
import os

import numpy as np
import pytest

N_INDEX, EMB = 14886, 128


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _shape(sharding, shape, dtype=np.float32):
    import jax
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("B", [64, 512])
def test_knn_topk_compiles(one_chip, B):
    from repro.kernels.knn_topk import knn_topk
    compiled = knn_topk.lower(
        _shape(one_chip, (B, EMB)), _shape(one_chip, (N_INDEX, EMB)),
        k=10, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("I", [16, 128])
@pytest.mark.parametrize("R", [64, 512])
def test_decision_megakernel_compiles(one_chip, R, I):
    """Every stage compiled in: GBM heads (the latency heads' 60 trees
    of depth 3), Eq. 2 admission, prefix affinity, LPT. The model count
    follows the roster: the paper's 4 models at I = 16, the hyperscale
    scenario's 16 at I = 128."""
    import jax

    from repro.kernels.decision_megakernel import decision_call
    from repro.serving.affinity import SIG_WIDTH, SKETCH_SLOTS
    M = 4 if I == 16 else 16
    trees, depth = 60, 3

    def s(shape, dtype=np.float32):
        return _shape(one_chip, shape, dtype)
    args = (s((1, R, EMB)), s((1, R), bool), s((1, R)), s((1, R)),
            s((1, R, SIG_WIDTH), np.int32),
            s((I,)), s((I,)), s((I,)), s((I,)), s((I,), bool),
            s((N_INDEX, EMB)), s((N_INDEX,)), s((N_INDEX, M)),
            s((N_INDEX, M)),
            s((I,), np.int32), s((I,), np.int32), s((I,)), s((I,)),
            s((I,)), s((I,)), s((I, SKETCH_SLOTS), np.int32),
            s((M, trees, 2 ** depth - 1), np.int32),
            s((M, trees, 2 ** depth - 1)), s((M, trees, 2 ** depth)),
            s((M,)))

    def step(*a):
        return decision_call(
            *a, k=10, eps=1e-6, weights=(0.4, 0.3, 0.3),
            latency_mode="full", lpt=True, budget_filter=True,
            w_aff=0.35, use_gbm=True, depth=depth, lr=0.15,
            interpret=False)
    compiled = jax.jit(step).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
