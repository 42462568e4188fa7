"""Deviceless compiles for one TPU v5e chip: every registered Pallas mix and
the main ``xla`` mixes, built by the bench's own backends at a 1 GiB f32
working set and compiled for a described (not attached) v5e.  A compile here
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
def one_chip(topo):
    """One described chip, with the persistent compilation cache off: a
    deviceless compile is written to it but cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def on_tpu(monkeypatch):
    """The platform as the bench sees it on the chip: JAX itself stays on
    the CPU here, so the backends' platform question is answered in the
    test."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _compile(backend, name, sharding):
    spec = BenchSpec(mixes=(name,), sizes=(NBYTES,), backend=backend.name,
                     passes=1)
    mix = get_mix(name)
    backend.validate(spec)
    case = backend.make_case(spec, mix, SHAPE, jnp.float32, 1)
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
            for a in backend.abstract_args(spec, mix, SHAPE, jnp.float32)]
    compiled = jax.jit(case).lower(*args).compile()
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes)
    assert used < HBM_BYTES, (name, used)
    return compiled.as_text()


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


@pytest.mark.parametrize("name", ["copy", "triad", "load_sum"])
def test_xla_mix_compiles_for_v5e(name, one_chip, on_tpu):
    hlo = _compile(get_backend("xla"), name, one_chip)
    assert "tpu_custom_call" not in hlo, name


def test_pallas_chase_refused_on_tpu(on_tpu):
    """Mosaic cannot lower the chase's dynamic_slice, and the interpreter
    must never stand in for it on the chip: the backend refuses it."""
    spec = BenchSpec(mixes=("latency_chase",), backend="pallas")
    with pytest.raises(BenchSpecError, match="R2"):
        get_backend("pallas").validate(spec)
