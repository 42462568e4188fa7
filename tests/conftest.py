"""Shared fixtures.  NOTE: no XLA_FLAGS here — tests see 1 device by design;
multi-device tests spawn subprocesses with the flag set explicitly."""
import pytest


@pytest.fixture(autouse=True)
def _ledger_isolation(tmp_path, monkeypatch):
    """Point the run ledger at a per-test temp dir: in-process CLI tests
    must not append BENCH_history/ records into the repo checkout."""
    from repro.obs import ledger
    monkeypatch.setenv(ledger.LEDGER_ENV, str(tmp_path / "BENCH_history"))

