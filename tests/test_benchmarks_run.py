"""Every entry of ``benchmarks/run.py`` at its quick size, as the suite runs
it: in this process, or in its own for the entries that force host devices.
Each prints its ``name,us_per_call,derived`` lines (``bench`` writes its
result file instead).  Artifacts go to the test's directory, never into the
checkout.  ``fig5`` has its own smoke test in ``test_bench_rw.py``."""
import json

import pytest

from benchmarks import run

IN_PROCESS = {name: fn for name, _, fn in run.IN_PROCESS_ENTRIES}
SUBPROCESS = dict(run.SUBPROCESS_ENTRIES)
ENTRIES = [name for name in (*IN_PROCESS, *SUBPROCESS) if name != "fig5"]
#: the figure modules among ``ENTRIES`` that read or write ``artifacts/``
ART_MODULES = ("fig2_hierarchy", "fig6_istream", "fig7_loaded_latency",
               "table1_machine")


@pytest.mark.parametrize("name", ENTRIES)
def test_benchmark_entry_runs_quick(name, tmp_path, monkeypatch, capsys):
    art = tmp_path / "artifacts"
    if name in SUBPROCESS:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / "jax_cache"))
        assert run._subproc(SUBPROCESS[name], quick=True), \
            capsys.readouterr().out
    else:
        monkeypatch.setattr(run, "ROOT", tmp_path)
        for mod in ART_MODULES:
            monkeypatch.setattr(f"benchmarks.{mod}.ART", art)
        IN_PROCESS[name](True)
    out = capsys.readouterr().out
    if name == "bench":
        doc = json.loads((art / "bench_quick.json").read_text())
        assert doc["points"] and all(p["gbps"] > 0 for p in doc["points"])
    else:
        lines = [line for line in out.splitlines()
                 if line.startswith(f"{name}/")]
        assert lines, out[-2000:]
        assert all(len(line.split(",")) >= 3 for line in lines), lines
