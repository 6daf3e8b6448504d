"""Guards of the device/compile-cache policy (ndstpu/engine/device.py):
no hidden CPU fallback for the accelerator engines, one resolver for the
persistent compile cache, compile-path degradation visible in reports."""

import json
import os
import pathlib
import subprocess
import sys

import pytest

from ndstpu.engine import device
from ndstpu.engine.session import Session

from test_obs import tiny_catalog

REPO = pathlib.Path(__file__).resolve().parent.parent


@pytest.mark.parametrize("platforms", ["", "tpu,cpu"])
def test_accel_engine_refuses_cpu_backend_without_pin(monkeypatch,
                                                      platforms):
    """No explicit cpu pin + a CPU default backend (no chip, or the
    chip held by another process): the accelerator engines refuse at
    session construction, naming the backend they found."""
    monkeypatch.setattr(device, "pinned_platforms", lambda: platforms)
    for engine in device.ACCEL_ENGINES:
        with pytest.raises(device.NoAcceleratorError,
                           match="default backend is 'cpu'"):
            Session(tiny_catalog(), backend=engine)
    # the numpy engine needs no device and never asks
    assert Session(tiny_catalog(), backend="cpu").backend == "cpu"


def test_accel_engine_accepts_cpu_backend_with_pin():
    """conftest pins the platform to cpu: that explicit pin is what
    lets the accelerator engines run on the CPU, and reports say cpu."""
    assert device.cpu_pinned()
    sess = Session(tiny_catalog(), backend="tpu")
    assert sess.sql("select count(*) as n from item").to_rows() == [(20,)]
    assert device.describe("tpu")["platform"] == "cpu"
    assert device.describe("cpu") == {
        "platform": "cpu", "device_kind": "numpy interpreter", "count": 0}


def test_compile_cache_resolver(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: the program sets no directory in
    code.  Unset: one fixed path inside the checkout.  Either way no
    Python frames go into what is lowered, and a session on a chip
    engine is what asks for both."""
    import jax
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    frames = ("jax_traceback_in_locations_limit", 0)
    monkeypatch.setenv(device.CACHE_ENV, str(tmp_path))
    assert device.configure_compile_cache() == str(tmp_path)
    assert calls == [frames]
    monkeypatch.delenv(device.CACHE_ENV)
    fixed = str(REPO / ".bench_cache" / "xla_cache_tpu")
    del calls[:]
    assert device.configure_compile_cache() == fixed
    assert calls == [("jax_compilation_cache_dir", fixed), frames]
    assert device.compile_cache_dir() == fixed
    del calls[:]
    monkeypatch.setattr(device, "wants_chip", lambda engine: True)
    monkeypatch.setattr(device, "require_accelerator", lambda engine: None)
    Session(tiny_catalog(), backend="tpu")
    assert calls == [("jax_compilation_cache_dir", fixed), frames]


def _lowered_keycmp_for_tpu(depth: int) -> str:
    """The TPU text of a program holding the keycmp kernel, traced
    afresh ``depth`` Python calls down."""
    if depth:
        return _lowered_keycmp_for_tpu(depth - 1)
    import jax
    import jax.numpy as jnp
    from ndstpu.ops import keycmp
    jax.clear_caches()
    x = jax.ShapeDtypeStruct((4096,), jnp.int32)
    k = jax.ShapeDtypeStruct((256,), jnp.int32)
    return jax.jit(lambda x, k, r: keycmp.match_rows(x, k, r)).trace(
        x, k, k).lower(lowering_platforms=("tpu",)).as_text()


def test_a_kernel_program_lowers_the_same_whoever_traced_it():
    """A Pallas kernel is serialized into its program with the Python
    frames of whoever traced it, so the compile-cache key of a
    kernel-bearing program followed the statement a process started
    at.  With the limit configure_compile_cache sets, the text lowered
    for the TPU is the same from any call depth; with JAX's default it
    is not (the fault is still there to guard against)."""
    import jax
    was = jax.config.jax_traceback_in_locations_limit
    try:
        jax.config.update("jax_traceback_in_locations_limit", 10)
        assert _lowered_keycmp_for_tpu(0) != _lowered_keycmp_for_tpu(5)
        jax.config.update("jax_traceback_in_locations_limit", 0)
        shallow = _lowered_keycmp_for_tpu(0)
        assert "tpu_custom_call" in shallow and "keycmp" in shallow
        assert shallow == _lowered_keycmp_for_tpu(5)
    finally:
        jax.config.update("jax_traceback_in_locations_limit", was)
        jax.clear_caches()


def test_build_jit_failure_on_accelerator_surfaces_in_summary(
        monkeypatch):
    """A whole-query program that does not build is answered by the
    eager path.  On a non-CPU platform that is a hidden fallback: it
    must reach the query summary as CompletedWithTaskFailures with the
    compiler's message, and count under engine.fallback.*."""
    from ndstpu.engine import jaxexec
    from ndstpu.harness.report import BenchReport

    sess = Session(tiny_catalog(), backend="tpu")
    monkeypatch.setattr(jaxexec, "default_platform", lambda: "tpu")

    def refuse(cp):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(sess._jax_executor(), "_build_jit", refuse)
    out = []
    summary = BenchReport({"engine": "tpu"}).report_on(
        lambda: out.append(sess.sql(
            "select sum(s_price) as total from sales").to_rows()),
        query_name="q")
    assert out == [[(sum(100 + i for i in range(60)),)]]
    assert summary["queryStatus"] == ["CompletedWithTaskFailures"]
    assert any("Mosaic failed to compile" in f
               for f in summary["taskFailures"])
    assert summary["metrics"][0]["counters"][
        "engine.fallback.compile"] >= 1
    assert summary["env"]["device"]["platform"] == "cpu"


@pytest.mark.slow
def test_chip_smoke_cpu_rehearsal(tmp_path):
    """chip_smoke.py end to end on the CPU at a tiny scale factor: every
    stage passes, and the result cannot be read as a chip pass."""
    env = dict(os.environ)
    env.pop("NDSTPU_WARM_REPLAY", None)   # conftest's test-suite default
    r = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"), "--rehearse-cpu",
         "--sf", "0.01", "--out", str(tmp_path / "out")],
        capture_output=True, text=True, env=env, cwd=str(tmp_path),
        timeout=1200)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    report_line, last_line = r.stdout.strip().splitlines()[-2:]
    # the last line carries exactly these keys; the report precedes it
    assert json.loads(last_line) == {"ok": False, "device": {
        "platform": "cpu", "kind": "cpu", "count": 4}}
    result = json.loads(report_line)
    assert result["ok"] is False
    assert result["rehearsal"]["ok"] is True
    assert result["device"]["platform"] == "cpu"
    assert all(s["ok"] for s in result["stages"].values())
