"""Compile-only checks of the device path for a described TPU v5e chip.

Nothing here runs on a chip: each test lowers one kernel or jitted step at
the shapes the full-scale Table-3 berkeley deployment gives
(``make_dataset("berkeley", scale=1.0)``: largest per-edge block class
npad = 2048, 2,169,894 packed nodes, 28,699 lixels at g = 50 m) and
compiles it with the TPU compiler for a chip that is described, not
attached. That catches what interpret mode cannot: block shapes the TPU
tiling refuses, kernels that overflow VMEM, f64 programs the compiler
cannot take. Dtypes are what the chip runs (``compat.device_x64`` is False
there): f32 values, int32 indices and time keys.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and several test workers import this
file.
"""
import os
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

W = 8  # window centers per flush
TQ = 128  # atom tile of the kernels
NPAD = 2048  # largest per-edge block size class at berkeley scale 1.0
HQ = 8  # DRFS depth of the served profile
K_S = K_T = 2  # triangular spatial x triangular temporal
K = K_S * K_T
G = 8  # edge groups per launch
N_NODES = 2_169_894  # packed position-major nodes at scale 1.0
N_EDGES = 4378
N_LIXELS = 28_699
M_ATOMS = 458_752  # atom block size class of a 400k-atom flush
LMAX = 12  # walk levels of the npad = 2048 class
# per-level node counts of the descending-n_pad packed layout (sum N_NODES)
LEVEL_NODES = (1087136, 543568, 271784, 135892, 67939, 33567, 15862, 7805,
               3791, 1781, 691, 78)
COMPILE_LIMIT_S = 240.0  # one compile; an f64 program can stall the compiler


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # described-chip compiles cannot be read back from a persistent cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype), sharding=sharding)


def _compile(lower):
    """Run ``lower().compile()`` with a time limit; return the executable."""
    out = {}

    def run():
        try:
            out["exe"] = lower().compile()
        except BaseException as e:  # re-raised in the test thread
            out["err"] = e

    th = threading.Thread(target=run, daemon=True)
    th.start()
    th.join(COMPILE_LIMIT_S)
    if th.is_alive():
        pytest.fail(f"TPU compile did not finish in {COMPILE_LIMIT_S:.0f} s")
    if "err" in out:
        raise out["err"]
    return out["exe"]


def _has_kernel(exe) -> bool:
    return "tpu_custom_call" in exe.as_text()


def _walk_offs(npad: int):
    offs, o = [], 0
    for lev in range(npad.bit_length()):
        offs.append(o)
        o += npad >> lev
    return tuple(offs)


def test_fused_walk_compiles(one_chip):
    from repro.kernels.fused_walk import fused_walk_pallas

    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    R2 = 2 * (2 * NPAD - 1)
    exe = _compile(lambda: fused_walk_pallas.lower(
        s((G, W * 2 * K_S, R2), jnp.float32),
        s((G, TQ), jnp.int32), s((G, TQ), jnp.int32), s((G, TQ), jnp.int32),
        s((G, TQ, K_S), jnp.float32),
        offs=_walk_offs(NPAD), tq=TQ, interpret=False, precise=False,
    ))
    assert _has_kernel(exe)


def test_fused_leaf_compiles(one_chip):
    from repro.kernels.fused_walk import fused_leaf_pallas

    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    R = ((1 << HQ) + 1) * 2
    exe = _compile(lambda: fused_leaf_pallas.lower(
        s((G, W * 2 * K, R), jnp.float32),
        s((G, TQ), jnp.int32), s((G, TQ), jnp.int32), s((G, TQ), jnp.int32),
        s((G, TQ, K_S), jnp.float32),
        s((W, K_T), jnp.float32), s((W, K_T), jnp.float32),
        tq=TQ, interpret=False, precise=False,
    ))
    assert _has_kernel(exe)


def test_dyn_leaf_query_compiles(one_chip):
    from repro.kernels.dyn_query import dyn_leaf_query_pallas

    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    R = ((1 << HQ) + 1) * 2
    exe = _compile(lambda: dyn_leaf_query_pallas.lower(
        s((G, W * 2 * K, R), jnp.float32),
        s((G, TQ), jnp.int32), s((G, TQ), jnp.int32), s((G, TQ), jnp.int32),
        s((G, W, TQ, K), jnp.float32), s((G, W, TQ, K), jnp.float32),
        tq=TQ, interpret=False,
    ))
    assert _has_kernel(exe)


def test_dyn_node_walk_compiles(one_chip):
    from repro.kernels.dyn_query import dyn_node_walk_pallas

    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    R2 = ((1 << (HQ + 1)) - 1) * 2
    exe = _compile(lambda: dyn_node_walk_pallas.lower(
        s((G, W * 2 * K_S, R2), jnp.float32),
        s((G, TQ), jnp.int32), s((G, TQ), jnp.int32), s((G, TQ), jnp.int32),
        s((G, TQ, K_S), jnp.float32),
        hq=HQ, tq=TQ, interpret=False,
    ))
    assert _has_kernel(exe)


def test_tree_query_compiles(one_chip):
    from repro.kernels.tree_query import tree_query_pallas

    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    lvl = NPAD.bit_length()
    exe = _compile(lambda: tree_query_pallas.lower(
        s((G, lvl, NPAD), jnp.float32), s((G, lvl, NPAD, 4 * K), jnp.float32),
        s((G, W, TQ), jnp.int32), s((G, W, TQ), jnp.int32),
        s((G, TQ), jnp.float32), s((G, TQ), jnp.float32),
        s((G, TQ), jnp.int32), s((G, TQ), jnp.float32),
        s((G, W, TQ, 4 * K), jnp.float32),
        tq=TQ, interpret=False, precise=False,
    ))
    assert _has_kernel(exe)


def test_packed_flush_compiles(one_chip):
    """The default executor's flush jit, f32 as on the chip, with the whole
    [L, W] heatmap and the full-scale node-value table."""
    from repro.core.jax_engine import FlatAtoms
    from repro.core.rfs import _get_packed

    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    _, _, flush = _get_packed()
    M = M_ATOMS
    fa = FlatAtoms(
        lixel=s((M,), jnp.int32), edge=s((M,), jnp.int32),
        side_feat=s((M,), jnp.int32), qs=s((M, K_S), jnp.float32),
        pos_hi=s((M,), jnp.float32), pos_lo1=s((M,), jnp.float32),
        lo1_right=s((M,), jnp.bool_), pos_lo2=s((M,), jnp.float32),
        valid=s((M,), jnp.bool_),
    )
    exe = _compile(lambda: flush.lower(
        s((W * 2 * K_S, 2 * N_NODES), jnp.float32), s((LMAX, N_EDGES), jnp.int32),
        fa, s((M,), jnp.int32), s((M,), jnp.int32),
        s((N_LIXELS, W), jnp.float32), max_levels=LMAX,
    ))
    mem = exe.memory_analysis()
    if mem is not None:  # a small share of one v5e chip's 16 GB
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 4e9, mem


def test_packed_tables_compiles(one_chip):
    """The dense window-table build at the largest window class, f32 as on
    the chip: no gather in the program, and a small share of the chip."""
    from repro.core.jax_engine import PackedForest, WindowBatch
    from repro.core.rfs import _get_packed

    assert sum(LEVEL_NODES) == N_NODES
    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    tables, _, _ = _get_packed()
    P = LEVEL_NODES[0]
    pf = PackedForest(
        pm_pos=s((P,), jnp.float32), pos_base=s((N_EDGES,), jnp.int32),
        pm_time=s((2, P), jnp.int32), pm_phi=s((4 * K, P), jnp.float32),
        n_pad=s((N_EDGES,), jnp.int32),
    )
    wb = WindowBatch(
        t_lo=s((2, 2 * W), jnp.int32), t_hi=s((2, 2 * W), jnp.int32),
        lo_right=s((2 * W,), jnp.bool_), half=s((2 * W,), jnp.int32),
        qt=s((2 * W, K_T), jnp.float32),
    )
    exe = _compile(lambda: tables.lower(
        pf, wb, level_nodes=LEVEL_NODES, k_t=K_T, out_dtype=None,
    ))
    assert " gather(" not in exe.as_text()
    mem = exe.memory_analysis()
    if mem is not None:
        assert mem.output_size_in_bytes + mem.temp_size_in_bytes < 4e9, mem


def test_dyn_flush_compiles(one_chip):
    """The served DRFS profile's flush jit (quantized, depth 8) at its
    largest window class, with a full pending buffer: the tree gathers and
    the pending phase's searches, doubling loop and block gathers."""
    from repro.core.jax_engine import FlatAtoms, FlatDynamicForest, WindowBatch
    from repro.core.rfs import _get_dyn

    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    _, _, flush = _get_dyn()
    Wd = 16  # largest window class of the served profile
    Lv, Np, Pp = HQ + 1, 665_600, 196_608  # levels, padded sealed / pending rows
    M = M_ATOMS // 2
    forest = FlatDynamicForest(
        time_lvl=s((2, Lv * Np), jnp.int32), pos_lvl=s((Lv * Np,), jnp.float32),
        cum_lvl=s((4 * K, Lv * Np), jnp.float32),
        node_ptr=s((N_EDGES * ((1 << Lv) - 1) + Lv,), jnp.int32),
        pend_ptr=s((N_EDGES + 1,), jnp.int32), pend_pos=s((Pp,), jnp.float32),
        pend_time=s((2, Pp), jnp.int32), pend_phi=s((4 * K, Pp), jnp.float32),
    )
    fa = FlatAtoms(
        lixel=s((M,), jnp.int32), edge=s((M,), jnp.int32),
        side_feat=s((M,), jnp.int32), qs=s((M, K_S), jnp.float32),
        pos_hi=s((M,), jnp.float32), pos_lo1=s((M,), jnp.float32),
        lo1_right=s((M,), jnp.bool_), pos_lo2=s((M,), jnp.float32),
        valid=s((M,), jnp.bool_),
    )
    wb = WindowBatch(
        t_lo=s((2, 2 * Wd), jnp.int32), t_hi=s((2, 2 * Wd), jnp.int32),
        lo_right=s((2 * Wd,), jnp.bool_), half=s((2 * Wd,), jnp.int32),
        qt=s((2 * Wd, K_T), jnp.float32),
    )
    lcum = s((Wd * 2 * K, 2 * N_EDGES * ((1 << HQ) + 1)), jnp.float32)
    exe = _compile(lambda: flush.lower(
        forest, fa, wb, (lcum,), s((M, 4), jnp.int32), s((N_LIXELS, Wd), jnp.float32),
        n_levels=Lv, hq=HQ, scan_steps=s((), jnp.int32), pend_steps=s((), jnp.int32),
        exact=False,
    ))
    mem = exe.memory_analysis()
    if mem is not None:
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 6e9, mem


@pytest.fixture(scope="module")
def four_chips(one_chip):
    """The described v5e 2x2 as a one-axis mesh, as ``TNKDE(shards=4)``
    builds it over the first four devices."""
    from jax.experimental import topologies
    from jax.sharding import Mesh

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    return Mesh(np.array(topo.devices).reshape(4), ("data",))


def test_sharded_drfs_programs_compile_for_a_2x2(four_chips):
    """The four-chip cell's programs at San Francisco's per-shard shapes
    (4,002 edges and 1,210,280 sealed events a shard, 64,776 lixels): the
    window tables and the flush, whose psum is its one all-reduce, each
    within its share of a chip beside the cached table sets."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.distributed import _get_programs
    from repro.core.jax_engine import FlatAtoms, FlatDynamicForest, WindowBatch

    slab, rep = NamedSharding(four_chips, P("data")), NamedSharding(four_chips, P())

    def s(shape, dt, sh=slab):  # slab arrays lead with the shard axis
        return _spec(sh, (4,) + shape if sh is slab else shape, dt)

    El, Lv, Np, Pp, L, Mp, Wd = 4002, HQ + 1, 1_310_720, 1_310_720, 64_776, 57_344, 16
    forest = FlatDynamicForest(
        time_lvl=s((2, Lv * Np), jnp.int32), pos_lvl=s((Lv * Np,), jnp.float32),
        cum_lvl=s((4 * K, Lv * Np), jnp.float32),
        node_ptr=s((El * ((1 << Lv) - 1) + Lv,), jnp.int32),
        pend_ptr=s((El + 1,), jnp.int32), pend_pos=s((Pp,), jnp.float32),
        pend_time=s((2, Pp), jnp.int32), pend_phi=s((4 * K, Pp), jnp.float32),
    )
    wb = WindowBatch(
        t_lo=s((2, 2 * Wd), jnp.int32, rep), t_hi=s((2, 2 * Wd), jnp.int32, rep),
        lo_right=s((2 * Wd,), jnp.bool_, rep), half=s((2 * Wd,), jnp.int32, rep),
        qt=s((2 * Wd, K_T), jnp.float32, rep),
    )
    fa = FlatAtoms(
        lixel=s((Mp,), jnp.int32), edge=s((Mp,), jnp.int32),
        side_feat=s((Mp,), jnp.int32), qs=s((Mp, K_S), jnp.float32),
        pos_hi=s((Mp,), jnp.float32), pos_lo1=s((Mp,), jnp.float32),
        lo1_right=s((Mp,), jnp.bool_), pos_lo2=s((Mp,), jnp.float32),
        valid=s((Mp,), jnp.bool_),
    )
    progs = _get_programs(four_chips, ("data",))
    with jax.enable_x64(False), jax.default_matmul_precision("highest"):
        tables = _compile(lambda: progs["dyn_tables"].lower(
            forest, wb, n_levels=Lv, hq=HQ, search_steps=8, steps_per_level=(),
            exact=False,
        ))
        lcum = s((Wd * 2 * K, 2 * El * ((1 << HQ) + 1)), jnp.float32)
        flush = _compile(lambda: progs["dyn_flush"].lower(
            forest, fa, wb, (lcum,), s((Mp, 4), jnp.int32),
            s((L, Wd), jnp.float32, rep), n_levels=Lv, hq=HQ, scan_steps=0,
            pend_steps=0, exact=False,
        ))
    assert "all-reduce" not in tables.as_text()
    assert "all-reduce" in flush.as_text()
    mem = tables.memory_analysis()
    if mem is not None:  # 7 cached W = 16 table sets (7.4 GB) sit beside it
        assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes) < 8e9, mem
    mem = flush.memory_analysis()
    if mem is not None:
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 6e9, mem
