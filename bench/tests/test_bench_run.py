"""`bench/run.py` refuses to measure off the chip, and `BENCHMARK.json`
names only files that exist (CPU)."""
import os
import subprocess
import sys
import types

import numpy as np
import pytest

import tiny  # noqa: F401  (puts the repo on sys.path)
from bench.harness import traffic
from bench.harness.spec import BENCH, Spec

RUN = os.path.join(BENCH, "run.py")


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [sys.executable, RUN, "--workload", "kron22.g500", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert res.returncode != 0
    assert "no TPU" in res.stderr
    assert res.stdout.strip() == ""


def test_an_unknown_device_kind_is_refused(monkeypatch):
    import jax
    sys.path.insert(0, BENCH)
    import run
    fake = types.SimpleNamespace(platform="tpu", device_kind="TPU v0 imagined")
    monkeypatch.setattr(jax, "devices", lambda *a: [fake])
    with pytest.raises(SystemExit, match="not in bench/peaks.json"):
        run.require_chip(1)
    fake.device_kind = "TPU v5 lite"
    with pytest.raises(SystemExit, match="needs 4 chips"):
        run.require_chip(4)
    devs, peaks = run.require_chip(1)
    assert peaks["hbm_bytes_per_s"] == 819e9 and devs == [fake]


def test_benchmark_names_only_what_exists():
    spec = Spec()
    bench = spec.bench
    assert bench["paths"] == ["bench"]
    for c in bench["configs"]:
        cfg = spec.config(c["name"])
        assert c["file"].startswith("bench/configs/")
        spec.generator(cfg["generator"])
        assert set(c["reduced"]) == set(cfg["reduced"])
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(Spec.reader(m["name"]).read)
        assert set(m.get("workloads", cells)) <= cells
    for w in bench["workloads"]:
        traffic.validate_mix(spec.traffic(w["traffic"]))
        assert spec.metrics(w["name"], False) and spec.metrics(w["name"], True)
    setup = [m for m in bench["end_to_end"] if m["name"] == "setup_s"]
    assert setup and "workloads" not in setup[0]



def test_search_keys_are_the_mixs_own():
    nonzero = np.arange(3, 500, 2)
    roots = dict(count=64, seed=27491095)
    a = traffic.search_keys(nonzero, roots)
    np.testing.assert_array_equal(a, traffic.search_keys(nonzero, roots))
    assert a.size == 64 == np.unique(a).size and np.isin(a, nonzero).all()
    assert traffic.search_keys(nonzero[:10], roots).size == 10
    with pytest.raises(ValueError, match="roots"):
        traffic.validate_mix(dict(roots=dict(count=4), check=dict(sample=1)))
