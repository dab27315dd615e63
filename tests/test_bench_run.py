"""benchmarks/run.py: a benchmark module that raises makes the run exit
non-zero in every mode, not only under --smoke."""
import importlib.util
import os
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAMES = ("bench_dispatch", "bench_throughput", "bench_serving",
         "bench_elastic", "bench_kernels")


def load_run():
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(REPO, "benchmarks", "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def fake_modules(monkeypatch, failing: str):
    pkg = types.ModuleType("benchmarks")
    monkeypatch.setitem(sys.modules, "benchmarks", pkg)
    for name in NAMES:
        mod = types.ModuleType(f"benchmarks.{name}")

        def run(smoke=False, name=name):
            if name == failing:
                raise RuntimeError(f"{name} broke")
            return [f"{name}_row,1.0,ok"]

        mod.run = run
        setattr(pkg, name, mod)
        monkeypatch.setitem(sys.modules, f"benchmarks.{name}", mod)


@pytest.mark.parametrize("smoke", [False, True], ids=["full", "smoke"])
def test_failing_module_exits_nonzero(monkeypatch, tmp_path, capsys, smoke):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    fake_modules(monkeypatch, failing="bench_serving")
    argv = [str(tmp_path / "out.json")] + (["--smoke"] if smoke else [])
    with pytest.raises(SystemExit) as exc:
        load_run().main(argv)
    assert exc.value.code == 1
    out = capsys.readouterr().out
    assert "bench_serving,ERROR,RuntimeError" in out
    assert "bench_kernels_row" in out          # later modules still ran


def test_clean_run_exits_zero(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    fake_modules(monkeypatch, failing="")
    load_run().main([str(tmp_path / "out.json")])
    assert (tmp_path / "out.json").exists()
