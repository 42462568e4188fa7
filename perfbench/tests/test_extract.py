"""The kernel the check runs is the kernel the window runs.

Taken out of a cell's own compiled case at its real size, the Pallas call
lowers for a described (not attached) TPU v5e to the same Mosaic payload as
the one inside the timed pass loop; the same mix on another tiling lowers to
another.  Nothing runs and nothing is measured; the topology is described
inside a fixture, never at import, since one process at a time may load the
TPU library."""
import re

import jax
import pytest
from jax.sharding import SingleDeviceSharding

from perfbench import extract, harness

#: the serialized Mosaic kernel of each Pallas call in a lowered program
PAYLOAD = re.compile(r'custom_call @tpu_custom_call\(.*?'
                     r'backend_config = "([^"]*)"')
PALLAS_CELLS = ["stream_copy", "membench_load_sum", "stream_runner"]


@pytest.fixture(scope="module")
def chip():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")    # else libtpu logs to /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _cases(workload):
    """(mix, its compiled case as the cell's backend makes it, abstract
    arguments at the real size) for each mix of the cell."""
    import jax.numpy as jnp
    from repro.bench import BenchSpec
    from repro.bench.backends import get_backend
    from repro.bench.mixes import get_mix
    cell = harness.load_cell(workload)
    shape = tuple(cell.config["shape"])
    dtype = jnp.dtype(cell.config["dtype"])
    backend = get_backend(cell.traffic["backend"])
    passes = int(cell.traffic["passes"])
    for m in cell.traffic.get("mixes") or [cell.traffic["mix"]]:
        spec = BenchSpec(mixes=(m,), sizes=(shape[0] * shape[1]
                                            * dtype.itemsize,),
                         dtype=dtype.name, backend=backend.name,
                         passes=passes)
        mix = get_mix(m)
        yield m, passes, backend.make_case(spec, mix, shape, dtype, passes), \
            backend.abstract_args(spec, mix, shape, dtype)


def _payloads(fn, args):
    return PAYLOAD.findall(jax.jit(fn).lower(*args).as_text())


@pytest.mark.parametrize("workload", PALLAS_CELLS)
def test_extracted_kernel_lowers_to_the_timed_kernel(workload, chip,
                                                     monkeypatch):
    from repro.kernels.membench import ops
    # the platform as the backends see it on the chip: compiled, not
    # interpreted
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for mix, passes, case, abstract in _cases(workload):
        args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip)
                for a in abstract]
        (eqn,) = extract.pallas_calls(case, args)
        timed = _payloads(case, args)
        alone = _payloads(extract.as_function(eqn), args)
        assert len(timed) == 1 and alone == timed, mix
        other = ops.make_timed_kernel(mix, block_rows=64, passes=passes)
        assert _payloads(other, args) != timed, mix
