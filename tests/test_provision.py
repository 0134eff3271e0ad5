"""Provisioning analog (reference ``deeplearning4j-aws``: Ec2BoxCreator /
ClusterSetup / HostProvisioner) + the YARN Kill CLI analog."""

import os
import subprocess
import sys
import time
from pathlib import Path

from deeplearning4j_tpu.parallel.procstate import FileStateTracker
from deeplearning4j_tpu.parallel.provision import (
    PodSliceProvisioner, PodSliceSpec)

REPO = Path(__file__).resolve().parents[1]


def test_pod_slice_spec_geometry():
    s = PodSliceSpec(accelerator_type="v5litepod-64")
    assert s.n_chips == 64 and s.n_hosts == 16       # v5e: 4-chip hosts
    assert PodSliceSpec(accelerator_type="v5litepod-8").n_hosts == 2


def test_create_and_launch_commands():
    spec = PodSliceSpec(name="slice1", accelerator_type="v5litepod-16",
                        zone="us-west4-a", spot=True)
    prov = PodSliceProvisioner(spec)
    create = prov.create_command()
    assert create[:5] == ["gcloud", "compute", "tpus", "tpu-vm", "create"]
    assert "--accelerator-type=v5litepod-16" in create
    assert "--spot" in create

    env = prov.launch_env(3, "10.0.0.2")
    assert env == {"JAX_COORDINATOR_ADDRESS": "10.0.0.2:8476",
                   "JAX_NUM_PROCESSES": "4", "JAX_PROCESS_ID": "3"}

    launch = prov.launch_command("-m deeplearning4j_tpu train", "$COORD")
    assert "JAX_COORDINATOR_ADDRESS=$COORD:8476" in launch
    assert "JAX_NUM_PROCESSES=4" in launch
    assert "agent-worker-number" in launch           # per-host process id


def test_render_script_is_wellformed(tmp_path):
    prov = PodSliceProvisioner(PodSliceSpec(accelerator_type="v5litepod-8"))
    path = prov.write_script(tmp_path / "up.sh", "https://example.com/r.git",
                             "-m deeplearning4j_tpu train")
    text = path.read_text()
    assert text.startswith("#!/usr/bin/env bash")
    assert "set -euo pipefail" in text
    assert "tpu-vm create" in text and "--worker=all" in text
    # remote worker-index lookup must be escaped for the outer shell
    assert "\\$(curl" in text
    assert os.access(path, os.X_OK)
    # the script parses as shell
    subprocess.run(["bash", "-n", str(path)], check=True, timeout=60)


def test_cli_scaleout_kill(tmp_path):
    state = tmp_path / "state"
    FileStateTracker(state)          # create the layout
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "-m", "deeplearning4j_tpu", "scaleout", "-t", "kill",
         "--state-dir", str(state)],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO)
    assert p.returncode == 0, p.stderr[-800:]
    assert FileStateTracker(state).is_done()


def test_kill_stops_running_master(tmp_path):
    """A kill issued while a master waits on an empty-but-unfinished job
    stream makes the whole run wind down (Kill.java behavior)."""
    from deeplearning4j_tpu.parallel.procrunner import ProcessDistributedRunner
    from deeplearning4j_tpu.parallel.scaleout import CollectionJobIterator

    state = tmp_path / "state"

    class NeverDone:
        """Iterator that claims more work is coming (streaming master)."""

        def next(self, worker_id=""):
            raise AssertionError("never dispenses")

        def has_next(self):
            return False

        def reset(self):
            pass

    runner = ProcessDistributedRunner(
        CollectionJobIterator(["a b", "c"]),
        "deeplearning4j_tpu.parallel.performers:WordCountPerformer",
        state_dir=state, n_workers=1,
        worker_env={"JAX_PLATFORMS": "cpu"})

    import threading
    killer = threading.Thread(
        target=lambda: (time.sleep(1.5), FileStateTracker(state).finish()),
        daemon=True)
    killer.start()
    t0 = time.time()
    runner.run(max_wall_s=60.0)
    # jobs drain quickly; kill (or natural finish) must not hang to the wall
    assert time.time() - t0 < 50.0
    assert FileStateTracker(state).is_done()


def test_core_counted_generations():
    """v4/v5p accelerator-type suffixes count TensorCores (2/chip), not
    chips; v5litepod suffixes count chips."""
    assert PodSliceSpec(accelerator_type="v4-8").n_chips == 4
    assert PodSliceSpec(accelerator_type="v4-8").n_hosts == 1
    assert PodSliceSpec(accelerator_type="v3-8").n_hosts == 1
    assert PodSliceSpec(accelerator_type="v5p-128").n_chips == 64
    assert PodSliceSpec(accelerator_type="v5litepod-64").n_chips == 64


def test_driver_wildcard_mesh_uses_all_devices():
    import jax

    from deeplearning4j_tpu.optimize import transforms as T
    from deeplearning4j_tpu.parallel.driver import Driver
    from deeplearning4j_tpu.parallel.mesh import MeshSpec

    import jax.numpy as jnp

    def loss_fn(p, xb, yb, key=None):
        return jnp.mean((xb @ p["w"] - yb) ** 2)

    d = Driver(loss_fn, T.sgd_lr(1e-2), mesh_spec=MeshSpec(tp=2))
    assert d.mesh.devices.size == len(jax.devices())   # wildcard dp fills


def _stub_gcloud(tmp_path):
    """A fake gcloud on PATH that logs argv and answers `describe` with an
    IP — lets apply()/teardown() integration-test without a cloud."""
    bindir = tmp_path / "bin"
    bindir.mkdir(exist_ok=True)
    log = tmp_path / "gcloud.log"
    stub = bindir / "gcloud"
    stub.write_text(
        "#!/usr/bin/env bash\n"
        f'echo "$@" >> {log}\n'
        'for a in "$@"; do\n'
        '  if [ "$a" = "describe" ]; then echo 10.1.2.3; fi\n'
        "done\n")
    stub.chmod(0o755)
    return bindir, log


def test_apply_dry_run_is_default_and_runs_nothing(tmp_path):
    prov = PodSliceProvisioner(PodSliceSpec(accelerator_type="v5litepod-8"))
    records = prov.apply("https://example.com/r.git", "-m deeplearning4j_tpu train")
    steps = [r["step"] for r in records]
    assert steps == ["create", "bootstrap", "resolve_coordinator", "launch"]
    assert all(r["rc"] is None for r in records)     # nothing executed


def test_apply_executes_against_stub_gcloud(tmp_path, monkeypatch):
    """--apply parity with ClusterSetup.java:24: the sequence actually
    executes (create -> bootstrap -> describe -> launch), the resolved
    coordinator IP feeds the launch env, and --kill tears down."""
    bindir, log = _stub_gcloud(tmp_path)
    monkeypatch.setenv("PATH", f"{bindir}:{os.environ['PATH']}")

    prov = PodSliceProvisioner(PodSliceSpec(
        name="s8", accelerator_type="v5litepod-8", zone="us-west4-a"))
    records = prov.apply("https://example.com/r.git",
                         "-m deeplearning4j_tpu train", dry_run=False)
    assert [r["rc"] for r in records] == [0, 0, 0, 0]
    logged = log.read_text()
    assert "create s8" in logged and "delete" not in logged
    assert logged.count("--worker=all") == 2         # bootstrap + launch
    launch = records[-1]["cmd"][-1]
    assert "JAX_COORDINATOR_ADDRESS=10.1.2.3:8476" in launch

    rec = prov.teardown(dry_run=False)
    assert rec["rc"] == 0
    assert "delete s8" in log.read_text()


def test_cli_provision_apply_and_kill_with_stub(tmp_path, monkeypatch):
    bindir, log = _stub_gcloud(tmp_path)
    env = dict(os.environ)
    env["PATH"] = f"{bindir}:{env['PATH']}"
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "-m", "deeplearning4j_tpu", "provision",
         "--name", "sX", "--accelerator-type", "v5litepod-8",
         "--repo-url", "https://example.com/r.git", "--apply"],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO)
    assert p.returncode == 0, p.stderr[-800:]
    assert "create sX" in log.read_text()

    p = subprocess.run(
        [sys.executable, "-m", "deeplearning4j_tpu", "provision",
         "--name", "sX", "--kill", "--apply"],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO)
    assert p.returncode == 0, p.stderr[-800:]
    assert "delete sX" in log.read_text()


def test_cli_provision_kill_dry_run_executes_nothing(tmp_path, monkeypatch):
    """--kill without --apply must only PRINT the delete command."""
    bindir, log = _stub_gcloud(tmp_path)
    env = dict(os.environ)
    env["PATH"] = f"{bindir}:{env['PATH']}"
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "-m", "deeplearning4j_tpu", "provision",
         "--name", "sY", "--kill"],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO)
    assert p.returncode == 0, p.stderr[-800:]
    assert "delete" in p.stdout and not log.exists()


def test_apply_timeout_names_step_and_keeps_audit_trail(tmp_path, monkeypatch):
    """A hung gcloud must surface as a RuntimeError naming the step, with
    the records-so-far attached — a half-created slice keeps its audit
    trail so the caller can tear down exactly what was attempted."""
    import pytest

    bindir = tmp_path / "bin"
    bindir.mkdir(exist_ok=True)
    stub = bindir / "gcloud"
    stub.write_text("#!/usr/bin/env bash\nsleep 5\n")
    stub.chmod(0o755)
    monkeypatch.setenv("PATH", f"{bindir}:{os.environ['PATH']}")

    prov = PodSliceProvisioner(PodSliceSpec(
        name="s8", accelerator_type="v5litepod-8", zone="us-west4-a"))
    with pytest.raises(RuntimeError, match="'create' timed out") as ei:
        prov.apply("https://example.com/r.git", "-m deeplearning4j_tpu train",
                   dry_run=False, timeout_s=0.3)
    err = ei.value
    assert isinstance(err.__cause__, subprocess.TimeoutExpired)
    assert [r["step"] for r in err.records] == ["create"]
    assert err.records[0]["rc"] is None          # never finished
    assert "teardown" in str(err)
