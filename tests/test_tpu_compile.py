"""Compile every Pallas kernel of the main paths for a described TPU v5e.

Interpret mode cannot see tiling or VMEM refusals; the TPU compiler can,
and it compiles for a chip that is described, not attached.  Each case
compiles one kernel at the size its cell runs (``interpret=False``), or
the mega-fleet decision that calls them, and asserts that the program
holds a ``tpu_custom_call`` and fits one chip's 16 GB of HBM.  Nothing runs, so these tests say nothing about results or
speed.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import dagsa_jit
from repro.kernels.bandwidth_solve import bandwidth_solve
from repro.kernels.compress_topk import sparsify_quantize
from repro.kernels.fedavg_reduce import fedavg_reduce, fedavg_segment_reduce
from repro.kernels.select_topk import best_bs_argmax, masked_bs_argmax
from repro.models.cnn import CNNConfig

HBM_BYTES = 16e9              # one v5e chip
MEGA_N, MEGA_M = 1_000_000, 100
N_CLIENTS, N_BS = 50, 8       # paper-default world


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")     # else the compiler logs
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep it out
        was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was_on)
            compilation_cache.reset_cache()


def _select(dtype, which):
    scale = (jax.ShapeDtypeStruct((MEGA_M,), jnp.float32)
             if dtype == jnp.int8 else None)
    snr = jax.ShapeDtypeStruct((MEGA_N, MEGA_M), dtype)
    if which == "masked":
        def fn(s, rem, sc):
            return masked_bs_argmax(s, rem, sc, interpret=False)
        return fn, (snr, jax.ShapeDtypeStruct((MEGA_N,), jnp.bool_), scale)

    def fn(s, sc):
        return best_bs_argmax(s, sc, interpret=False)
    return fn, (snr, scale)


def _bandwidth(k, u, coeff_dtype=jnp.bfloat16, tcomp_shape=None):
    def fn(c, t, m, bw, lo):
        return bandwidth_solve(c, t, m, bw, lo=lo, interpret=False)
    # the greedy's trial solve: [K, U] coefficients (the bf16 plane, or the
    # f32 member lists of the compact trials) and trial mask; the dense
    # trials share one [U] tcomp row
    return fn, (jax.ShapeDtypeStruct((k, u), coeff_dtype),
                jax.ShapeDtypeStruct(tcomp_shape or (k, u), jnp.float32),
                jax.ShapeDtypeStruct((k, u), jnp.bool_),
                jax.ShapeDtypeStruct((k,), jnp.float32),
                jax.ShapeDtypeStruct((k,), jnp.float32))


def _fc1_leaf(lead):
    """The paper-scale CNN's largest leaf, fc1 [1568, 64], with a lead axis."""
    cfg = CNNConfig.paper_scale()
    return jax.ShapeDtypeStruct((lead, cfg.flat_dim, cfg.hidden), jnp.float32)


def _fedavg():
    cfg = CNNConfig.paper_scale()

    def fn(g, c, sel, sizes):
        return fedavg_reduce(g, c, sel, sizes, interpret=False)
    return fn, ({"w": jax.ShapeDtypeStruct((cfg.flat_dim, cfg.hidden),
                                           jnp.float32)},
                {"w": _fc1_leaf(N_CLIENTS)},
                jax.ShapeDtypeStruct((N_CLIENTS,), jnp.bool_),
                jax.ShapeDtypeStruct((N_CLIENTS,), jnp.float32))


def _fedavg_segment():
    def fn(e, c, assign, sizes):
        return fedavg_segment_reduce(e, c, assign, sizes, interpret=False)
    return fn, ({"w": _fc1_leaf(N_BS)}, {"w": _fc1_leaf(N_CLIENTS)},
                jax.ShapeDtypeStruct((N_CLIENTS, N_BS), jnp.bool_),
                jax.ShapeDtypeStruct((N_CLIENTS,), jnp.float32))


def _sparsify_int8():
    n, d = N_CLIENTS, 50_000

    def fn(x, thresh, scale, u):
        return sparsify_quantize(x, thresh, scale, u, quantize=True,
                                 interpret=False)
    return fn, (jax.ShapeDtypeStruct((n, d), jnp.float32),
                jax.ShapeDtypeStruct((n,), jnp.float32),
                jax.ShapeDtypeStruct((n,), jnp.float32),
                jax.ShapeDtypeStruct((n, d), jnp.float32))


def _mega_fleet_decision():
    """One mega-fleet DAGSA decision on the Pallas route, with the user
    chunk the million-user sweep passes: the engine, not the test, picks
    each kernel's block sizes here."""
    def fn(snr, coeff, tcomp, bw, necessary, keys):
        return dagsa_jit._schedule_batch(
            snr, coeff, tcomp, bw, necessary, 300, keys, backend="pallas",
            interpret=False, selection_block=8192)
    plane = jax.ShapeDtypeStruct((1, MEGA_N, MEGA_M), jnp.bfloat16)
    return fn, (plane, plane,
                jax.ShapeDtypeStruct((1, MEGA_N), jnp.float32),
                jax.ShapeDtypeStruct((1, MEGA_M), jnp.float32),
                jax.ShapeDtypeStruct((1, MEGA_N), jnp.bool_),
                jax.ShapeDtypeStruct((1, 2), jnp.uint32))


CASES = {
    "masked_bs_argmax-1e6x100-bf16": lambda: _select(jnp.bfloat16, "masked"),
    "masked_bs_argmax-1e6x100-int8": lambda: _select(jnp.int8, "masked"),
    "best_bs_argmax-1e6x100-bf16": lambda: _select(jnp.bfloat16, "best"),
    "best_bs_argmax-1e6x100-int8": lambda: _select(jnp.int8, "best"),
    "bandwidth_solve-8x50": lambda: _bandwidth(8, 50),
    "bandwidth_solve-8x50-shared-tcomp": lambda: _bandwidth(8, 50,
                                                            tcomp_shape=(50,)),
    "bandwidth_solve-100x1e6": lambda: _bandwidth(MEGA_M, MEGA_N),
    "bandwidth_solve-100x128": lambda: _bandwidth(MEGA_M, 128, jnp.float32),
    "fedavg_reduce-fc1-50": _fedavg,
    "fedavg_segment_reduce-fc1-50x8": _fedavg_segment,
    "sparsify_quantize-int8-50x5e4": _sparsify_int8,
    "dagsa_decision-mega-fleet-pallas": _mega_fleet_decision,
}

# Leading kernel operands a case's program must hold: the decision solves
# Eq. (11) over compact [M, 128] member lists, and keeps the dense [M, N]
# continuation for a BS that fills its list.
OPERANDS = {"dagsa_decision-mega-fleet-pallas": (
    f"f32[{MEGA_M},128]", f"f32[{MEGA_M},{MEGA_N}]")}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, shapes = CASES[case]()
    placed = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        shapes)
    compiled = jax.jit(fn).lower(*placed).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, (
        f"{case}: no Pallas kernel in the compiled program")
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    for shape in OPERANDS.get(case, ()):
        assert any(f"operand_layout_constraints={{{shape}" in line
                   for line in calls), f"{case}: no kernel call on {shape}"
    mem = compiled.memory_analysis()
    total = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
             + mem.output_size_in_bytes)
    assert total < HBM_BYTES, f"{case}: {total / 1e9:.2f} GB > 16 GB"
