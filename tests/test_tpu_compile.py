"""K1 compiles for a TPU v5e chip, described and not attached.

The TPU compiler is installed next to JAX, so a kernel can be compiled for a
described ``v5e:2x2`` topology without the chip. That catches what interpret
mode cannot: primitives Mosaic does not lower (an in-kernel scatter did not)
and operands that overflow fast memory (the scalar-prefetched column table,
sized by pool capacity, once outgrew SMEM). Capacities: 65,536 and
8,388,608, the oncology deployment's capacity at 1,048,576 seed agents. The
SIR step compiles too, at 4,096 agents and at the epidemiology-sir cell's
1,048,576, where the neighbor sweep's window loop must read no gather, and
so does the oncology-growth cell's step at its 2,097,152 slots.

The topology is described in a process of its own (``described``), never in
a test worker: only one process at a time may load the TPU library, and once
loaded it stays, and adds a device plane with no ops to every later profiler
trace of its process, which a traced CPU run there would read as a device
that ran nothing.
"""

import contextlib
import functools
import itertools
import multiprocessing
import re
from concurrent.futures import ProcessPoolExecutor
from unittest import mock

import pytest

ADH = ((0.5, 0.1), (0.1, 0.7))
MAXB = 64

# -- run in the described chip's process --------------------------------------

_one_chip = None   # a sharding on the described chip, or why there is none


def _describe():
    global _one_chip
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler to describe with
        _one_chip = f"no v5e:2x2 topology can be described here: {e}"
        return
    # compiles for a described chip are written to the persistent cache but
    # cannot be read back without one; keep them out of it
    jax.config.update("jax_enable_compilation_cache", False)
    _one_chip = SingleDeviceSharding(topo.devices[0])


def _why_not():
    return _one_chip if isinstance(_one_chip, str) else None


def _k1(capacity, adhesion, maxb=MAXB):
    """(padded size, custom call present, output bytes) of K1 compiled for
    the described chip, its column map ``maxb`` wide."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import collision_force as k1

    n_pad = k1.padded_size(capacity, maxb)
    data = jax.ShapeDtypeStruct((8, n_pad), jnp.float32, sharding=_one_chip)
    cols = jax.ShapeDtypeStruct((n_pad // k1.BLOCK, maxb), jnp.int32,
                                sharding=_one_chip)
    step = jax.jit(lambda d, c: k1.collision_force_kernel(
        d, c, k_rep=2.0, adhesion=adhesion, adhesion_band=0.4,
        interpret=False))
    compiled = step.lower(data, cols).compile()
    return (n_pad, "tpu_custom_call" in compiled.as_text(),
            compiled.memory_analysis().output_size_in_bytes)


@functools.lru_cache(maxsize=None)
def _sir_step_text(scopes=True, counters=True, n=4096) -> str:
    """The SIR step at ``n`` agents compiled for the described chip, with
    or without the engine's phase scopes and the sweep's work counters; at
    1,048,576 agents it is the epidemiology-sir cell's step (bench/configs:
    a cube of side n^(1/3)·5 µm, query blocks of 4,096). Kept per process:
    several tests read the same step."""
    import jax
    import jax.numpy as jnp
    from repro.core import EngineConfig, Simulation, grid
    from repro.core.behaviors import Infection, RandomWalk

    with contextlib.ExitStack() as patches:
        if not scopes:
            patches.enter_context(mock.patch.object(
                jax, "named_scope", lambda name: contextlib.nullcontext()))
        if not counters:
            zero = jnp.zeros((), jnp.int32)
            patches.enter_context(mock.patch.object(
                grid, "fused_sweep_work",
                lambda *a, **k: (jnp.zeros((), jnp.float32), zero, zero,
                                 zero)))
        side = max(80.0, n ** (1 / 3) * 5.0)
        sim = Simulation(
            EngineConfig(capacity=n, domain_lo=(0.0,) * 3,
                         domain_hi=(side,) * 3, interaction_radius=3.0,
                         use_forces=False,
                         query_chunk=1024 if n == 4096 else 4096),
            [RandomWalk(sigma=0.8),
             Infection(radius=3.0, beta=0.25, recovery_time=40)])
        state = jax.eval_shape(lambda: sim.init_state(
            jnp.zeros((n, 3), jnp.float32), jnp.ones((n,), jnp.float32)))
        state = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=_one_chip), state)
        return sim._step_fn.lower(state).compile().as_text()


@functools.lru_cache(maxsize=None)
def _oncology_step_text(agents=1_048_576) -> str:
    """The oncology-growth cell's step (bench/configs/oncology.json: K1
    forces at the pinned column-map width, growth, division, death) at
    ``agents`` seeds, twice that many slots, compiled for the described
    chip."""
    import json
    from pathlib import Path

    import jax
    import jax.numpy as jnp
    from bench import deploy

    config = json.loads((Path(__file__).resolve().parent.parent / "bench"
                         / "configs" / "oncology.json").read_text())
    dep = deploy.deployment(config, agents=agents)
    sim = dep.simulation()
    state = jax.eval_shape(lambda: sim.init_state(
        jnp.zeros((agents, 3), jnp.float32), jnp.ones((agents,), jnp.float32)))
    state = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=_one_chip),
        state)
    # this process's backend is the CPU, where K1 would take interpret mode
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        return sim._step_fn.lower(state).compile().as_text()


# -- the tests -----------------------------------------------------------------

@pytest.fixture(scope="module")
def described():
    """A spawned process holding the described chip; ``submit`` runs one
    of the functions above there."""
    spawn = multiprocessing.get_context("spawn")
    with ProcessPoolExecutor(1, mp_context=spawn,
                             initializer=_describe) as pool:
        why = pool.submit(_why_not).result()
        if why:
            pytest.skip(why)
        yield pool


@pytest.mark.parametrize("adhesion", [None, ADH], ids=["repulsion", "adhesion"])
@pytest.mark.parametrize("capacity", [65_536, 8_388_608])
def test_k1_compiles_for_v5e(described, capacity, adhesion):
    n_pad, custom_call, out_bytes = described.submit(
        _k1, capacity, adhesion).result()
    assert custom_call
    assert out_bytes == 8 * n_pad * 4


def test_k1_compiles_for_v5e_at_the_widest_column_map(described):
    """At the widest column map the capacity ladder reaches (one SMEM slice
    per row block) and a pool of 2**22 slots, whose column blocks it can
    all hold, every launch's table slice still fits."""
    from repro.kernels import collision_force as k1
    width = k1.SMEM_TABLE_ENTRIES
    n_pad, custom_call, out_bytes = described.submit(
        _k1, 1 << 22, None, width).result()
    assert custom_call and n_pad == 1 << 22
    assert out_bytes == 8 * n_pad * 4


def _without_metadata(text: str) -> str:
    """Compiled HLO without its metadata (source op names, which carry the
    phase scopes) and the file table."""
    text = re.sub(r",? metadata=\{[^}]*\}", "", text)
    return re.sub(r"\n\nFileNames\n.*?\n\n\n", "\n\n", text, flags=re.S)


def test_phase_scopes_leave_the_v5e_program_unchanged(described):
    """The engine's named scopes are metadata: without them the chip's
    compiler emits the same ops, fused the same way."""
    scoped = described.submit(_sir_step_text).result()
    unscoped = described.submit(_sir_step_text, scopes=False).result()
    assert _without_metadata(unscoped) == _without_metadata(scoped)


def _sweep_loops(text: str) -> dict:
    """The neighbor sweep's tile loops, by path (``window_path``,
    ``gather_path``): each loop's condition and body and every computation
    they call, in order, without metadata, with names numbered by first
    use, so that a program that only adds work outside the loops gives the
    same text."""
    comps, name = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%(\S+) .*\{$", line)
        if head:
            name, comps[head.group(1)] = head.group(1), []
        if name is not None:
            comps[name].append(line)
            name = None if line == "}" else name
    loops = re.findall(r"condition=%(\S+), body=%(\S+), "
                       r"metadata=\{op_name=\"[^\"]*neighbor_sweep/(\w+)/while\"",
                       text)
    paths = [path for _, _, path in loops]
    assert sorted(paths) == ["gather_path", "window_path"], paths
    out = {}
    for cond, body, path in loops:
        order = []

        def visit(comp):
            if comp not in order:
                order.append(comp)
                for line in comps[comp]:
                    for callee in re.findall(
                            r"(?:calls|to_apply|body|condition)=%([\w.\-]+)",
                            line):
                        visit(callee)

        visit(cond)
        visit(body)
        lines = []
        for comp in order:
            # reads of the loop's tuple cost nothing, and their listed
            # order varies: list each run of them by tuple index
            for gte, run in itertools.groupby(
                    comps[comp], lambda line: " get-tuple-element(" in line):
                run = list(run)
                lines += sorted(run, key=lambda line: re.sub(
                    r"%[\w.\-]+", "%", line.split(" = ", 1)[1])) if gte else run
        loop = _without_metadata("\n".join(lines))
        loop = re.sub(r"\b(param_\d+|arg_tuple)\.\d+", r"\1", loop)
        ids = {}
        out[path] = re.sub(r"%[\w.\-]+", lambda m: ids.setdefault(
            m.group(0), f"%v{len(ids)}"), loop)
    return out


def test_sweep_counters_leave_the_v5e_sweep_loop_unchanged(described):
    """The sweep's work counters are computed from the grid tables outside
    its tile loops: without them the chip's compiler emits the same loops,
    scheduled the same way."""
    counted = described.submit(_sir_step_text).result()
    uncounted = described.submit(_sir_step_text, counters=False).result()
    assert _sweep_loops(counted) == _sweep_loops(uncounted)


def test_the_cells_window_path_reads_no_gather_on_v5e(described):
    """The epidemiology-sir cell's step compiles for the described chip at
    its capacity, 1,048,576 agents, and the sweep's window loop reads every
    stencil column as a slice: no gather op in it, where the per-row
    fallback loop gathers."""
    loops = _sweep_loops(described.submit(_sir_step_text,
                                          n=1_048_576).result())
    assert " gather(" not in loops["window_path"]
    assert " gather(" in loops["gather_path"]


def _unlabelled(text: str) -> list:
    """Scatter, sort and gather ops of the compiled step that lost their
    ``op_name``, so their time would read as unscoped."""
    from bench import trace
    labels = trace.hlo_labels(text)
    return [name for name, kinds in trace.hlo_kinds(text).items()
            if kinds & {"scatter", "sort", "gather"} and name not in labels]


_CELL_STEPS = {"epidemiology-sir": functools.partial(_sir_step_text,
                                                     n=1_048_576),
               "oncology-growth": _oncology_step_text}


@pytest.mark.parametrize("cell", sorted(_CELL_STEPS))
def test_the_cells_grid_build_runs_no_loop_on_v5e(described, cell):
    """The grid build reads its tables from the sorted keys' run
    boundaries: in both cells' steps, compiled for the described chip, no
    while loop lies under ``grid_build`` (a binary search lowers to one, a
    chain of dependent gathers), and the build's scatters, like every
    scatter, sort and gather, keep their ``op_name``. Their indices come
    sorted, so the compiler sorts none of them first: such a sort costs
    time and megabytes of program, which the device's peak memory counts."""
    text = described.submit(_CELL_STEPS[cell]).result()
    loops = re.findall(r" while\(.*op_name=\"([^\"]*grid_build/[^\"]*)\"",
                       text)
    assert not loops, loops
    assert not _unlabelled(text)
    assert re.search(r" scatter\(.*op_name=\"[^\"]*grid_build/", text)
    assert not re.search(r" sort\(.*op_name=\"[^\"]*grid_build/scatter", text)


def test_the_oncology_cells_step_compiles_for_v5e(described):
    """The oncology-growth cell's step compiles for the described chip at
    its capacity, 2,097,152 slots, with K1 at the pinned column-map width:
    its launches sit in the ``k1`` scope, inside the sweep's, and no
    scatter, sort or gather has lost its ``op_name`` (the column map's
    per-row-block scatter once did, and its time read as unscoped)."""
    text = described.submit(_oncology_step_text).result()
    assert not _unlabelled(text)
    calls = [line for line in text.splitlines()
             if "custom_call_target=\"tpu_custom_call\"" in line]
    assert calls
    assert all("neighbor_sweep/k1/" in line for line in calls)
    assert "/colmap_build/" in text
    assert all(f"/commit/{part}/" in text
               for part in ("deaths", "compaction", "births"))


def test_the_tpu_library_stays_out_of_the_test_process(described):
    with open("/proc/self/maps") as maps:
        assert not [line for line in maps if "libtpu" in line]
