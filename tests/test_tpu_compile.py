"""Deviceless compiles for one TPU v5e chip: every registered Pallas and
``xla`` mix, built by the bench's own backends at a 1 GiB f32
working set and compiled for a described (not attached) v5e; and the
``all_reduce`` collective over the four chips of a described v5e 2x2 host;
and the Runner's working-set fill (``core.buffers._fill``).  A compile here
is what the chip's compiler accepts or refuses; nothing runs and nothing is
measured.  The topology is described inside a fixture, never at import: only
one process at a time may load the TPU library."""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.bench import BenchSpec, BenchSpecError
from repro.bench.backends import get_backend
from repro.bench.mixes import get_mix, mix_names
from repro.core.buffers import working_set_shape

NBYTES = 2**30
SHAPE = working_set_shape(NBYTES)
HBM_BYTES = 16 * 10**9          # one v5e chip (Google Cloud, "TPU v5e")
PALLAS_MIXES = [m for m in mix_names("pallas") if not get_mix(m).chase]
#: the Pallas mixes whose kernel writes arrays, and those that return a scalar
ARRAY_MIXES = [m for m in PALLAS_MIXES if get_mix(m).writes_per_elem]
SCALAR_MIXES = [m for m in PALLAS_MIXES if not get_mix(m).writes_per_elem]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")    # else libtpu logs to /tmp
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_compile_cache():
    """The persistent compilation cache off: a deviceless compile is
    written to it but cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo, no_compile_cache):
    """One described chip."""
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    """The platform as the bench sees it on the chip: JAX itself stays on
    the CPU here, so the backends' platform question is answered in the
    test."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _compiled(backend, name, sharding, nbytes=NBYTES, passes=1, unroll=1,
              **knobs):
    spec = BenchSpec(mixes=(name,), sizes=(nbytes,), backend=backend.name,
                     passes=passes, unroll=unroll, **knobs)
    mix = get_mix(name)
    shape = working_set_shape(nbytes)
    backend.validate(spec)
    case = backend.make_case(spec, mix, shape, jnp.float32, passes)
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
            for a in backend.abstract_args(spec, mix, shape, jnp.float32)]
    compiled = jax.jit(case).lower(*args).compile()
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes)
    assert used < HBM_BYTES, (name, used)
    return compiled


def _compile(backend, name, sharding):
    return _compiled(backend, name, sharding).as_text()


def _computations(hlo: str) -> dict[str, list[str]]:
    """The instruction lines of each computation of a module's HLO text,
    by name; the entry computation is also under ``"ENTRY"``."""
    comps: dict[str, list[str]] = {}
    lines = None
    for line in hlo.splitlines():
        m = re.match(r"(ENTRY )?%(\S+) \(", line)
        if m:
            lines = comps.setdefault(m.group(2), [])
            if m.group(1):
                comps["ENTRY"] = lines
        elif line == "}":
            lines = None
        elif lines is not None:
            lines.append(line)
    return comps


def _while_body(comps: dict[str, list[str]]) -> list[str]:
    """The body of the pass loop: the one ``while`` of the entry."""
    (body,) = [m.group(1) for line in comps["ENTRY"]
               for m in [re.search(r" while\(.*body=%(\S+?)[,\s]", line)]
               if m]
    return comps[body]


def _defines(lines: list[str], pattern: str) -> list[str]:
    """The instructions among ``lines`` whose result and op match
    ``pattern`` (``<shape>{<layout>} <op>(``)."""
    return [line for line in lines
            if re.match(rf"\s*(ROOT )?%\S+ = {pattern}", line)]


@pytest.mark.parametrize("name", PALLAS_MIXES)
def test_pallas_mix_compiles_for_v5e(name, one_chip, on_tpu):
    """It compiles, under the names a profiler trace of the chip shows: the
    pass loop's module ``jit_membench_passloop_<mix>`` and the kernel's
    custom call ``%membench_<mix>.N``."""
    hlo = _compile(get_backend("pallas"), name, one_chip)
    assert hlo.startswith(f"HloModule jit_membench_passloop_{name},"), \
        hlo.splitlines()[0]
    assert re.search(rf'%membench_{name}\.\d+ = .*'
                     rf'custom_call_target="tpu_custom_call"', hlo), name


@pytest.mark.parametrize("name", mix_names("xla"))
def test_xla_mix_compiles_for_v5e(name, one_chip, on_tpu):
    hlo = _compile(get_backend("xla"), name, one_chip)
    assert "tpu_custom_call" not in hlo, name


def test_pallas_chase_refused_on_tpu(on_tpu):
    """Mosaic cannot lower the chase's dynamic_slice, and the interpreter
    must never stand in for it on the chip: the backend refuses it."""
    spec = BenchSpec(mixes=("latency_chase",), backend="pallas")
    with pytest.raises(BenchSpecError, match="R2"):
        get_backend("pallas").validate(spec)


@pytest.mark.parametrize("name,rows", [("copy", 4096), ("triad", 2048),
                                       ("rw_2to1", 2048)])
def test_budget_tile_compiles_for_v5e(name, rows, one_chip, on_tpu):
    """The default tile at 1 GiB is the VMEM budget's, larger than the
    128-row floor, and Mosaic fits it in its scoped VMEM: the pass loop
    compiles and holds the same HBM as with the floor tile."""
    backend = get_backend("pallas")
    spec = BenchSpec(mixes=(name,), sizes=(NBYTES,), backend="pallas")
    assert backend._resolve(spec, get_mix(name), SHAPE, jnp.float32) == rows
    budget, floor = (
        _compiled(backend, name, one_chip, passes=2, **knobs)
        .memory_analysis() for knobs in ({}, {"block_rows": 128}))
    for field in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes"):
        assert getattr(budget, field) == getattr(floor, field), field


def test_tile_past_vmem_is_refused_for_v5e(one_chip, on_tpu):
    """The described compile sees a tile that overflows VMEM: four times
    triad's budget tile (six 4 MiB buffers) is refused."""
    with pytest.raises(jax.errors.JaxRuntimeError, match="memory space vmem"):
        _compiled(get_backend("pallas"), "triad", one_chip, passes=2,
                  block_rows=8192)


WS = rf"f32\[{SHAPE[0]},{SHAPE[1]}\]"      # a working set's shape in HLO


@pytest.mark.parametrize("unroll", [1, 2, 4])
@pytest.mark.parametrize("name", ARRAY_MIXES)
def test_compiled_array_passloop_reads_working_set_in_place(
        name, unroll, one_chip, on_tpu):
    """A compiled kernel's sweeps are chained through an optimization
    barrier, not a write into the working set: the entry makes no copy of
    any working set for the loop to write, the loop body runs ``unroll``
    kernel calls a trip (none hoisted, none merged), each returning one
    working set per write stream, and the temporaries are the rotating
    output slots alone.  ``passes`` is two trips at every ``unroll``: XLA
    inlines a loop of one trip, leaving no body to read."""
    writes = int(get_mix(name).writes_per_elem)
    compiled = _compiled(get_backend("pallas"), name, one_chip,
                         passes=2 * unroll, unroll=unroll)
    comps = _computations(compiled.as_text())
    assert not _defines(comps["ENTRY"], rf"{WS}\S* copy(-start)?\("), name
    result = ", ".join([rf"{WS}\S*"] * writes)
    if writes > 1:
        result = rf"\({result}\)"
    kernels = _defines(_while_body(comps),
                       rf"{result} custom-call\(.*\), "
                       rf'custom_call_target="tpu_custom_call"')
    assert len(kernels) == unroll, (name, unroll, len(kernels))
    assert all(f"%membench_{name}." in k.split("=")[0] for k in kernels)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= unroll * writes * NBYTES + 2**20, (name, unroll, temp)


@pytest.mark.parametrize("name", SCALAR_MIXES)
def test_compiled_scalar_passloop_still_writes_working_set(name, one_chip,
                                                          on_tpu):
    """The scalar path keeps its one-element write into the carried
    buffer: at 1 MiB a scalar mix's pass loop stages the working set in
    VMEM (memory space ``S(1)``) and updates it there every pass."""
    nbytes = 2**20
    rows, cols = working_set_shape(nbytes)
    hlo = _compiled(get_backend("pallas"), name, one_chip,
                    nbytes=nbytes, passes=4).as_text()
    comps = _computations(hlo)
    vmem = rf"f32\[{rows},{cols}\]\{{1,0:T\(8,128\)S\(1\)\}}"
    assert len(_defines(_while_body(comps),
                        rf"{vmem} dynamic-update-slice\(")) == 1
    assert _defines(comps["ENTRY"], rf"{vmem} copy-done\(")


@pytest.mark.parametrize("unroll", [1, 2])
def test_all_reduce_passloop_compiles_for_v5e_2x2(topo, no_compile_cache,
                                                   monkeypatch, unroll):
    """The ``all_reduce`` mix's case on the sharded backend, over the four
    described chips of a v5e 2x2 host, 1 GiB in all (256 MiB a rank): the
    module ``jit_collective_passloop_all_reduce``, whose loop body runs
    ``unroll`` all-reduces of a whole message a trip (none hoisted, none
    merged), whose entry makes no copy of the message, and whose
    arguments, output and temporaries fit each chip."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.bench.backends import ShardedBackend
    backend = ShardedBackend()          # its own mesh cache, on topo's chips
    monkeypatch.setattr(backend, "_mesh_devices", lambda: list(topo.devices))
    passes = 8
    spec = BenchSpec(mixes=("all_reduce",), sizes=(NBYTES,),
                     backend="sharded", devices=4, passes=passes,
                     unroll=unroll)
    case = backend.make_case(spec, get_mix("all_reduce"), SHAPE,
                             jnp.float32, passes)
    x = jax.ShapeDtypeStruct(SHAPE, jnp.float32, sharding=NamedSharding(
        backend._mesh(4), P("d", None)))
    compiled = jax.jit(case).lower(x).compile()
    hlo = compiled.as_text()
    assert hlo.startswith("HloModule jit_collective_passloop_all_reduce,"), \
        hlo.splitlines()[0]
    message = rf"f32\[{SHAPE[0] // 4},{SHAPE[1]}\]"
    comps = _computations(hlo)
    exchanges = _defines(_while_body(comps),
                         rf"{message}\S* all-reduce(-start)?\(")
    assert len(exchanges) == unroll, (unroll, exchanges)
    assert not _defines(comps["ENTRY"], rf"{message}\S* copy(-start)?\(")
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes == NBYTES // 4
    assert ma.temp_size_in_bytes <= unroll * NBYTES // 4 + 2**20
    assert (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes) < HBM_BYTES


@pytest.mark.parametrize("dtype,hlo_type", [(jnp.float32, "f32"),
                                            (jnp.bfloat16, "bf16")])
def test_working_set_fill_compiles_to_one_fusion_for_v5e(dtype, hlo_type,
                                                        one_chip):
    """The Runner's 1 GiB working-set build, as the chip compiles it: one
    fusion writes the whole buffer from the four-value cycle, and the only
    allocation of the working set's size is that output."""
    from repro.core import buffers
    shape = working_set_shape(NBYTES, dtype)
    compiled = buffers._fill.lower(
        jax.ShapeDtypeStruct((4,), dtype, sharding=one_chip), shape).compile()
    full = rf"{hlo_type}\[{shape[0]},{shape[1]}\]"
    entry = _computations(compiled.as_text())["ENTRY"]
    assert len(_defines(entry, rf"{full}\S* fusion\(")) == 1
    assert len(_defines(entry, rf"{full}\S* \S+\(")) == 1
    ma = compiled.memory_analysis()
    assert ma.output_size_in_bytes == NBYTES
    assert ma.temp_size_in_bytes <= 2**20
