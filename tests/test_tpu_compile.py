"""Compile-only checks of the scan's device programs for a TPU v5e.

The TPU compiler is installed with JAX, and it compiles for a chip that is
described and not attached.  These tests lower the real bucket runners
(the jitted ``(init, scan)`` pair of :func:`repro.core.fastpath._runner_fns`)
at bucket shapes taken from ``chip_smoke.py``'s phases and compile them for
one chip of a described ``v5e:2x2`` topology.  That catches what interpret
mode cannot -- block shapes off the (8, 128) tiling, ops Mosaic does not
lower, programs that do not fit -- without a chip.  Nothing runs.

The topology is described inside a module fixture (never at import): only
one process may hold the TPU library, and under pytest-xdist every worker
imports this file.  The persistent compilation cache is off around these
compiles, since an entry written for a described chip cannot be read back.
"""

from functools import partial

import pytest

jax = pytest.importorskip("jax")

from repro.core import fastpath as fp  # noqa: E402
from repro.kernels import ops  # noqa: E402

# the scan donates its carry planes, which rarely alias an output
pytestmark = pytest.mark.filterwarnings("ignore:Some donated buffers")

# (feature mask, n, nodes, slots, fns, kq, window, fc ring, episodes,
#  copies, extra steps) -- bucket keys the chip smoke run dispatches
PALLAS_BASE = (0x0, 512, 4, 8, 16, 64, 10, 1, 1, 1, 0)      # phase A
PALLAS_FC = (0x2, 1024, 4, 8, 16, 64, 10, 1, 1, 1, 0)       # phase A, FC
DYN_PULL = (0x82, 512, 8, 8, 16, 32, 10, 1, 1, 1, 256)      # phase B
PLANET_STREAM = (0x280, 4096, 128, 1, 16384, 1, 10, 1, 1, 1, 256)  # phase C


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile_runner(key, bsz, sharding):
    """AOT-compile one bucket's ``(init, scan)`` pair for ``sharding``'s
    device; returns the scan executable."""
    init_fn, scan_fn = fp._runner_fns(key)

    def place(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=sharding), tree)

    use64 = fp._use64(fp._mask_features(key[0]))
    with fp._x64_ctx(use64):
        specs = place({k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                       for k, v in fp._alloc_bucket_inputs(key, bsz).items()})
        init_fn.lower(specs).compile()
        clk, ctr = place(jax.eval_shape(init_fn, specs))
        return scan_fn.lower(clk, ctr, specs).compile()


@pytest.mark.parametrize("key", [PALLAS_BASE, PALLAS_FC],
                         ids=["base_pull", "base_pull_fc"])
def test_pallas_event_step_compiles(one_chip, monkeypatch, key):
    # steer the dispatcher to its TPU branch: the described chip is not
    # the process's default backend
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    _, step_kw = fp._runner_kwargs(key)
    assert ops.event_step_path(**step_kw) == "pallas"
    scan_c = _compile_runner(key, 16, one_chip)
    assert "tpu_custom_call" in scan_c.as_text()


@pytest.mark.parametrize("key", [DYN_PULL, PLANET_STREAM],
                         ids=["f64_autoscale_kills", "f64_planet_stream"])
def test_jnp_bucket_runner_compiles(one_chip, monkeypatch, key):
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    _, step_kw = fp._runner_kwargs(key)
    assert ops.event_step_path(**step_kw) == "jnp"
    bsz = 1 if fp._mask_features(key[0])["stream"] else 2
    scan_c = _compile_runner(key, bsz, one_chip)
    assert "tpu_custom_call" not in scan_c.as_text()
    assert "f64" in scan_c.as_text()       # the float64 family stays f64


def test_pallas_kernel_compiles_outside_the_runner(one_chip):
    # the kernel alone, compiled (not interpreted) at a phase-A shape
    from repro.kernels.event_step import event_step_pallas

    key = PALLAS_BASE
    _, step_kw = fp._runner_kwargs(key)
    init_fn, _ = fp._runner_fns(key)
    specs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one_chip)
             for k, v in fp._alloc_bucket_inputs(key, 8).items()}
    clk, ctr = jax.eval_shape(init_fn, specs)
    clk, ctr = (jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
                for x in (clk, ctr))
    kern = jax.jit(partial(event_step_pallas, interpret=False, **step_kw))
    text = kern.lower(clk, ctr, specs).compile().as_text()
    assert "tpu_custom_call" in text
