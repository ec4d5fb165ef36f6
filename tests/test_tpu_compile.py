"""The main-path Pallas kernels compile for a TPU v5e chip.

Interpret mode, which runs every other kernel test, accepts block shapes
that Mosaic refuses.  These tests hand the TPU compiler a described (not
attached) v5e chip and compile each kernel at the serving width: D=128,
posting lists of L=128, C=4096 clusters, a batch of 64 queries, 16 probes
and the q8 pipeline's candidate width k2=24 (k=10).  A compiled program
that holds ``tpu_custom_call`` kept its Mosaic kernel.  The fused top-k
kernels also compile at the served shapes: a padded batch of 16 or 32
queries at nprobe 128, with their counters among the outputs, and at a
search service's width: k2 = 2000 (k = 1000) at D = 64, where the merge
is the buffered one and must fit the default scoped VMEM.

The topology is described inside a module fixture, never at import time:
only one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

C, L, D, B, P, K2 = 4096, 128, 128, 64, 16, 24
SERVE_B, SERVE_P = (16, 32), 128
WIDE_K2, WIDE_D = 2000, 64
K_CENTS = 1024


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip is written to the persistent
    # cache but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _shapes(sharding, *specs):
    return [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in specs]


def _scan_cases():
    from repro.kernels import ivf_scan as f32
    from repro.kernels import ivf_scan_q8 as q8
    from repro.kernels import kmeans_assign, kmeans_mstep

    i32, f, b8, i8 = jnp.int32, jnp.float32, jnp.bool_, jnp.int8
    probes = [((B, P), i32), ((B, P), b8), ((B, D), f)]
    q8_payload = [((C, L, D), i8), ((C, 1, 1), f), ((C, L), f), ((C, D), f)]

    def served(b, d=D):
        return [((b, SERVE_P), i32), ((b, SERVE_P), b8), ((b, d), f)]

    wd = WIDE_D
    wide_q8 = [((C, L, wd), i8), ((C, 1, 1), f), ((C, L), f), ((C, wd), f)]
    served_cases = {}
    for b in SERVE_B:
        served_cases[f"ivf_scan_topk_b{b}"] = (
            lambda *a: f32.ivf_scan_topk(*a, k2=K2),
            [((C, L, D), f), ((C, L), i32)] + served(b))
        served_cases[f"ivf_scan_q8_topk_b{b}"] = (
            lambda *a: q8.ivf_scan_q8_topk(*a, k2=K2),
            q8_payload + [((C, L), i32)] + served(b))
        served_cases[f"ivf_scan_topk_k2000_b{b}"] = (
            lambda *a: f32.ivf_scan_topk(*a, k2=WIDE_K2),
            [((C, L, wd), f), ((C, L), i32)] + served(b, wd))
        served_cases[f"ivf_scan_q8_topk_k2000_b{b}"] = (
            lambda *a: q8.ivf_scan_q8_topk(*a, k2=WIDE_K2),
            wide_q8 + [((C, L), i32)] + served(b, wd))
    return served_cases | {
        "ivf_scan_topk": (
            lambda *a: f32.ivf_scan_topk(*a, k2=K2),
            [((C, L, D), f), ((C, L), i32)] + probes),
        "ivf_scan_q8_topk": (
            lambda *a: q8.ivf_scan_q8_topk(*a, k2=K2),
            q8_payload + [((C, L), i32)] + probes),
        "ivf_scan": (f32.ivf_scan, [((C, L, D), f)] + probes),
        "ivf_scan_q8": (q8.ivf_scan_q8, q8_payload + probes),
        "ivf_scan_clustermajor": (
            f32.ivf_scan_clustermajor,
            [((C, L, D), f), ((256,), i32), ((256, B), b8), ((B, D), f)]),
        "kmeans_assign_update": (
            kmeans_assign.kmeans_assign_update,
            [((16384, D), f), ((K_CENTS, D), f), ((), i32)]),
        "kmeans_mstep": (
            kmeans_mstep.kmeans_mstep,
            [((K_CENTS, D), f), ((K_CENTS,), f), ((K_CENTS, D), f)]),
    }


@pytest.mark.parametrize("name", [
    "ivf_scan_topk", "ivf_scan_q8_topk", "ivf_scan", "ivf_scan_q8",
    "ivf_scan_clustermajor", "kmeans_assign_update", "kmeans_mstep",
    "ivf_scan_topk_b16", "ivf_scan_topk_b32", "ivf_scan_q8_topk_b16",
    "ivf_scan_q8_topk_b32", "ivf_scan_topk_k2000_b16",
    "ivf_scan_topk_k2000_b32", "ivf_scan_q8_topk_k2000_b16",
    "ivf_scan_q8_topk_k2000_b32"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, specs = _scan_cases()[name]
    compiled = jax.jit(fn).lower(*_shapes(one_chip, *specs)).compile()
    assert "tpu_custom_call" in compiled.as_text(), name
    if "topk" in name:                     # candidates and the counters
        d, i, stats = compiled.out_info
        assert d.shape == i.shape and stats.shape == (5,), name


@pytest.mark.parametrize("kind", ["f32", "q8"])
def test_wide_topk_program_does_not_grow_with_k2(one_chip, kind):
    """The buffered merge loops over its stack and over its bitonic stages,
    so the lowered kernel is the same size at k2 = 256 and at k2 = 2000: a
    merge or an extraction unrolled k2 times (or a sort stage per lane
    distance) would make the wider one several times larger."""
    from repro.kernels import ivf_scan as f32
    from repro.kernels import ivf_scan_q8 as q8

    f, i32, b8 = jnp.float32, jnp.int32, jnp.bool_
    wd = WIDE_D
    probe = [((C, L), i32), ((SERVE_B[0], SERVE_P), i32),
             ((SERVE_B[0], SERVE_P), b8), ((SERVE_B[0], wd), f)]
    if kind == "f32":
        fn, payload = f32.ivf_scan_topk, [((C, L, wd), f)]
    else:
        fn = q8.ivf_scan_q8_topk
        payload = [((C, L, wd), jnp.int8), ((C, 1, 1), f), ((C, L), f),
                   ((C, wd), f)]
    size = {k2: len(jax.jit(lambda *a, k2=k2: fn(*a, k2=k2))
                    .lower(*_shapes(one_chip, *payload, *probe)).as_text())
            for k2 in (256, WIDE_K2)}
    assert size[WIDE_K2] < 1.05 * size[256], size
