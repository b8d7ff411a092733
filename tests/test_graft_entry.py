"""Driver-contract tests for __graft_entry__.py.

``dryrun_multichip`` is a dry run on VIRTUAL CPU devices and nothing
else: it always provisions its own n-device CPU platform in a child,
whatever this process has (conftest gives it 8), and never initialises
a backend of its own for it.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import __graft_entry__


@pytest.mark.parametrize("n_devices", [8, 16])
def test_dryrun_runs_on_its_own_virtual_cpu_devices(n_devices, capfd):
    # 8 == what this process has, 16 > it: same path either way
    __graft_entry__.dryrun_multichip(n_devices)
    out = capfd.readouterr().out
    assert f"dryrun_multichip OK on cpu (virtual devices): " \
           f"{n_devices}-device mesh" in out


def test_dryrun_child_is_cpu_pinned_and_parent_touches_no_backend(
        monkeypatch):
    """One process per chip: the child is pinned to the CPU backend with
    its own device count (it cannot want a chip), and the parent decides
    nothing by asking JAX for devices."""
    import subprocess

    import jax

    monkeypatch.setattr(
        jax, "devices",
        lambda *a, **kw: (_ for _ in ()).throw(AssertionError(
            "the parent asked JAX for devices")))
    seen = {}

    def fake_run(cmd, env=None, **kw):
        seen["env"] = env
        return subprocess.CompletedProcess(cmd, 0, stdout="", stderr="")

    monkeypatch.setattr(subprocess, "run", fake_run)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=8")
    __graft_entry__.dryrun_multichip(4)
    assert seen["env"]["JAX_PLATFORMS"] == "cpu"
    assert seen["env"]["XLA_FLAGS"] == \
        "--xla_force_host_platform_device_count=4"


def test_dryrun_child_failure_raises(monkeypatch):
    import subprocess

    monkeypatch.setattr(
        subprocess, "run",
        lambda cmd, **kw: subprocess.CompletedProcess(
            cmd, 3, stdout="", stderr="boom"))
    with pytest.raises(RuntimeError, match=r"rc=3.*boom"):
        __graft_entry__.dryrun_multichip(4)


def test_entry_compiles_single_chip():
    import jax

    fn, (variables, batch) = __graft_entry__.entry()
    out = jax.jit(fn)(variables, batch)
    assert out.shape[0] == batch.shape[0]
    assert out.ndim == 2
