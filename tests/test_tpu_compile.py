"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Nothing runs here: each test lowers and compiles one kernel at the sizes
the solver uses, for a v5e device that is described, not attached.  The
TPU compiler refuses what interpret mode accepts — slices that are not
tile-aligned, primitives Mosaic cannot lower, more VMEM than a kernel may
use — so these tests guard the chip path without a chip.  Each asserts
that the compiled program holds the kernel (``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import network
from repro.kernels import batched_solve as bs
from repro.kernels import blocked_sets as bset
from repro.kernels import sparse_solve as ss

# the stepsize-ladder width the engine vmaps the traffic solve over
LADDER = 12


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import compilation_cache, topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile_text(fn, sharding, *args):
    """Compile ``fn`` for the described chip; args are (shape, dtype)."""
    specs = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in args]
    return jax.jit(fn).lower(*specs).compile().as_text()


def _kernel_named(text, name):
    """The compiled program holds the kernel as an instruction named after
    it, the name a profiler trace gives the kernel's device operations (a
    kernel vmapped outside any jit, as here, is named ``vmap_<name>_``)."""
    return re.search(rf"^\s*%(vmap_)?{name}_?(\.\d+)? = \S+ custom-call\(.*"
                     r'custom_call_target="tpu_custom_call"', text,
                     re.MULTILINE) is not None


F32 = jnp.float32


@pytest.mark.parametrize("V,B", [(100, 30 * 3), (300, 3 * 3), (11, 32 * 3 * 3)],
                         ids=["table2-Vp128", "metro-dense-Vp384",
                              "abilene-Vp128"])
def test_lu_factor_compiles(one_chip, V, B):
    fn = jax.vmap(functools.partial(bs.lu_factor, interpret=False))
    text = _compile_text(fn, one_chip, ((LADDER, B, V, V), F32))
    assert _kernel_named(text, "lu_factor")


@pytest.mark.parametrize("trans", [0, 1])
def test_lu_solve_compiles(one_chip, trans):
    fn = functools.partial(bs.lu_solve, trans=trans, interpret=False)
    text = _compile_text(fn, one_chip, ((90, 100, 100), F32), ((90, 100), F32))
    assert _kernel_named(text, "lu_solve")


@pytest.mark.parametrize("trans,reverse,clamp",
                         [(1, False, False), (0, True, True)],
                         ids=["traffic", "marginals"])
@pytest.mark.parametrize("V,A", [(100, 30), (300, 3), (11, 32 * 3)],
                         ids=["Vp128", "Vp384", "abilene-Vp128"])
def test_chain_solve_compiles(one_chip, V, A, trans, reverse, clamp):
    K = 3
    fn = jax.vmap(functools.partial(bs.chain_solve, trans=trans,
                                    reverse=reverse, clamp=clamp,
                                    interpret=False))
    text = _compile_text(fn, one_chip, ((LADDER, A, K, V, V), F32),
                         ((LADDER, A, K, V), F32), ((LADDER, A, K, V), F32))
    assert _kernel_named(text, "chain_solve")


@pytest.mark.parametrize("V", [300, 1000], ids=["metro-sw-300",
                                                "metro-sw-1000"])
@pytest.mark.parametrize("reverse,clamp", [(False, False), (True, True)],
                         ids=["traffic", "marginals"])
def test_chain_solve_bsr_compiles(one_chip, V, reverse, clamp):
    inst = network.metro_instance("sw", V)
    A, K = inst.r.shape[0], inst.stage_mask.shape[1]
    blk_nbr = np.asarray(inst.blk_nbr)
    NB, BD = blk_nbr.shape
    bsz = ss.SPARSE_BLOCK

    def fn(bvals, base, mult):
        return ss.chain_solve_bsr(bvals, jnp.asarray(blk_nbr), base, mult,
                                  reverse=reverse, clamp=clamp,
                                  interpret=False)

    text = _compile_text(jax.vmap(fn), one_chip,
                         ((LADDER, A, K, NB, BD, bsz, bsz), F32),
                         ((LADDER, A, K, V), F32), ((LADDER, A, K, V), F32))
    assert _kernel_named(text, "chain_solve_bsr")


def test_tagged_pallas_compiles(one_chip):
    V, B = 4096, 9
    Vp, W = bset.padded_nodes(V)
    fn = functools.partial(bset.tagged_pallas, V=V, interpret=False)
    text = _compile_text(fn, one_chip, ((B, Vp, W), jnp.uint32),
                         ((B, Vp, W), jnp.uint32))
    assert "tpu_custom_call" in text


def _computations(text):
    """Optimized HLO text -> {computation name: its instruction lines}."""
    comps, name = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(line)
    return comps


def _called(line):
    """Names of the computations an instruction calls."""
    names = re.findall(r"(?:calls|to_apply|body|condition|true_computation|"
                       r"false_computation)=%([\w.\-]+)", line)
    for group in re.findall(r"branch_computations=\{([^}]*)\}", line):
        names += re.findall(r"%([\w.\-]+)", group)
    return names


def _reachable(comps, root):
    seen, todo = set(), [root]
    while todo:
        name = todo.pop()
        if name not in seen:
            seen.add(name)
            todo += [c for line in comps[name] for c in _called(line)]
    return seen


def _holds_kernel(comps, root):
    return any('custom_call_target="tpu_custom_call"' in line
               for name in _reachable(comps, root) for line in comps[name])


def test_scan_chunk_loop_exits_at_the_latch(one_chip, monkeypatch):
    """The chunk's step loop, the one loop whose body runs the GP step's
    kernels, tests the done latch (a boolean of its state) in its
    condition: steps after the latch are never run.  A loop of fixed trip
    count would run the kernels on every frozen step."""
    from repro.core import engine, gp
    from repro.kernels import ops

    inst = network.table_ii_instance("abilene", seed=0)
    acc = engine.resolve_accel(True)
    carry = gp._init_carry(inst, gp.init_phi(inst), accel=acc)
    scalars = (jnp.float32(0.1), jnp.float32(1e-4), jnp.int32(40),
               jnp.int32(400))
    spec = functools.partial(
        jax.tree_util.tree_map,
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x),
                                       sharding=one_chip))
    # the kernels' dispatch follows the backend at trace time: steer it to
    # the chip's path, with no trace of the CPU path left in the jit caches
    jax.clear_caches()
    monkeypatch.setattr(ops, "INTERPRET", False)
    monkeypatch.setattr(ops, "_PALLAS_DEFAULT", True)
    try:
        text = gp._scan_chunk.lower(
            spec(inst), spec(carry), *spec(scalars), None, None, length=3,
            solver="batched_lu", accel=acc).compile().as_text()
    finally:
        jax.clear_caches()
    comps = _computations(text)
    entry = re.search(r"^ENTRY %([\w.\-]+) ", text, re.MULTILINE).group(1)
    loops = [re.search(r"condition=%([\w.\-]+), body=%([\w.\-]+)", line)
             .groups() for name in _reachable(comps, entry)
             for line in comps[name] if " while(" in line]
    step_loops = [cond for cond, body in loops if _holds_kernel(comps, body)]
    assert len(step_loops) == 1
    assert any(re.search(r"= pred\[\]\S* get-tuple-element\(", line)
               for line in comps[step_loops[0]])
