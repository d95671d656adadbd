"""``bench.py``'s own control flow, with every measurement stubbed out:
a block that raises must fail the run (after the JSON line), and an
emulated-mesh child must never be able to reach for the parent's chip."""

import json
import subprocess
import types

import pytest

import bench
from learning_jax_sharding_tpu.utils import compile_cache


@pytest.fixture
def stubbed(monkeypatch):
    """Every ``bench_*`` block returns at once; nothing touches a device or
    turns the compile cache on."""
    headline = {
        "tflops": 1.0, "seconds_per_forward": 1e-3, "collectives": {},
        "axis_volume": {},
    }
    for name in dir(bench):
        if name.startswith("bench_"):
            monkeypatch.setattr(bench, name, lambda *a, **k: None)
    monkeypatch.setattr(bench, "bench_attention", lambda *a, **k: headline)
    monkeypatch.setattr(bench, "_device_ready", lambda: True)
    monkeypatch.setattr(bench, "_diagnosis_block", lambda volume: {})
    monkeypatch.setattr(compile_cache, "place_compile_cache", lambda: "")


def test_clean_run_prints_the_json_line_and_returns(stubbed, capsys):
    bench.main()
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line["metric"] == "case6_attention_tflops_per_chip"


def test_a_block_that_raises_fails_the_run_after_the_json_line(
    stubbed, monkeypatch, capsys
):
    def boom():
        raise RuntimeError("kernel refused")

    monkeypatch.setattr(bench, "bench_fleet", boom)
    with pytest.raises(SystemExit) as exit_info:
        bench.main()
    assert exit_info.value.code == 1
    captured = capsys.readouterr()
    assert json.loads(captured.out.splitlines()[-1])["value"] == 1.0
    assert "fleet bench FAILED: RuntimeError: kernel refused" in captured.err
    assert "FAILED blocks: fleet" in captured.err


def test_emulated_child_names_the_cpu_platform(monkeypatch):
    seen = {}

    def fake_run(cmd, **kwargs):
        seen.update(cmd=cmd, env=kwargs["env"])
        return types.SimpleNamespace(returncode=0, stdout="ok\n", stderr="")

    monkeypatch.setenv("JAX_PLATFORMS", "")
    monkeypatch.setattr(subprocess, "run", fake_run)
    assert bench._emulated_child("perf_fleet.py", "--bench-lines") == "ok\n"
    assert seen["env"]["JAX_PLATFORMS"] == "cpu"
    assert seen["cmd"][1].endswith("scripts/perf_fleet.py")
    assert seen["cmd"][2:] == ["--bench-lines"]


def test_emulated_child_raises_on_a_failed_child(monkeypatch):
    monkeypatch.setattr(
        subprocess, "run",
        lambda cmd, **kw: types.SimpleNamespace(
            returncode=3, stdout="", stderr="a\nb\nlast words"
        ),
    )
    with pytest.raises(RuntimeError, match="exited 3: a\nb\nlast words"):
        bench._emulated_child("replay.py", "--json")
