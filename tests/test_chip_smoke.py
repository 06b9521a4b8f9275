"""Rehearse ``chip_smoke.py`` on the CPU at a tiny size.

The script's sizes and required platform live in its ``SIZE`` dict; these
tests replace it, run every phase in this process (the Pallas kernels in
interpret mode) and check the script's output contract. On a host with
no TPU the unmodified script must stop at its first phase.
"""
import importlib.util
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = {
    "platform": "cpu",
    "dense_axes": {"congestion.u_mean": [0.2, 0.4],
                   "congestion.k_burst": [0.5, 1.5]},
    "dense_iters": 40, "dense_warmup": 5, "dense_checked": 2,
    "nodes": 32, "tenants": 4, "ranks": 8,
    "iters": 30, "warmup": 5,
    "u_mean": [0.2, 0.3], "k_burst": [1.0],
    "checked_u_mean": 0.3, "checked_k_burst": 1.0,
}


def _load():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_phases_run_in_order_at_tiny_size(monkeypatch, capsys):
    smoke = _load()
    assert set(TINY) == set(smoke.SIZE)
    monkeypatch.setattr(smoke, "SIZE", TINY)
    smoke.main()
    lines = capsys.readouterr().out.strip().splitlines()
    assert [ln.split(":")[0] for ln in lines[:-1]] == [
        "device", "dense_sweep", "cotenant_sweep", "advise"]
    last = json.loads(lines[-1])
    assert last["ok"] is True
    assert last["device"]["platform"] == "cpu"
    assert last["device"]["count"] >= 1


def test_smoke_refuses_a_host_without_a_tpu(capsys):
    smoke = _load()
    assert smoke.SIZE["platform"] == "tpu"
    with pytest.raises(smoke.SmokeFailure, match="not 'tpu'"):
        smoke.main()
    assert '"ok"' not in capsys.readouterr().out
