"""Cluster provisioning — the TPU-native analog of the reference's AWS
module (``deeplearning4j-aws``): ``Ec2BoxCreator.java:19,59`` (create spot/
on-demand instances), ``provision/ClusterSetup.java:24`` +
``HostProvisioner`` (SSH fan-out setup), and the YARN ``Client`` launch
path.

There is no cloud reachable from this environment, so the module does what
those classes actually owe the framework: given a cluster spec, produce the
exact commands/scripts that create a TPU pod slice and bring the training
job up on every host — creation command, per-host bootstrap, and a
coordinated multi-host launch with the ``jax.distributed`` env contract
(``JAX_COORDINATOR_ADDRESS``/``JAX_NUM_PROCESSES``/``JAX_PROCESS_ID``) that
``parallel.mesh.initialize_multihost`` consumes.  Everything is returned as
data (and optionally written as a shell script) so it is testable offline
and runnable verbatim where a cloud is present.
"""

from __future__ import annotations

import dataclasses
import shlex
import subprocess
from pathlib import Path

__all__ = ["PodSliceSpec", "PodSliceProvisioner"]

# The accelerator-type numeric suffix counts CHIPS for v5e (v5litepod-N)
# but TENSORCORES (2 per chip) for v2/v3/v4/v5p; every generation here
# packs 4 chips per host.
_SUFFIX_COUNTS_CHIPS = {"v5litepod"}
_CHIPS_PER_HOST = 4


@dataclasses.dataclass(frozen=True)
class PodSliceSpec:
    """What ``Ec2BoxCreator``'s (ami, size, numBoxes) tuple becomes on TPU:
    a named slice of an accelerator type in a zone."""

    name: str = "dl4j-tpu-slice"
    accelerator_type: str = "v5litepod-64"   # BASELINE.json's scaling target
    zone: str = "us-west4-a"
    runtime_version: str = "tpu-ubuntu2204-base"
    project: str | None = None
    spot: bool = False                        # Ec2BoxCreator spot parity
    coordinator_port: int = 8476

    @property
    def generation(self) -> str:
        return self.accelerator_type.rsplit("-", 1)[0]

    @property
    def n_chips(self) -> int:
        suffix = int(self.accelerator_type.rsplit("-", 1)[1])
        if self.generation in _SUFFIX_COUNTS_CHIPS:
            return suffix
        return max(1, suffix // 2)       # core-counted generations

    @property
    def n_hosts(self) -> int:
        return max(1, self.n_chips // _CHIPS_PER_HOST)


class PodSliceProvisioner:
    """Renders the create/bootstrap/launch command set for a pod slice."""

    def __init__(self, spec: PodSliceSpec):
        self.spec = spec

    # -- creation (Ec2BoxCreator.create parity) -------------------------
    def create_command(self) -> list[str]:
        s = self.spec
        cmd = ["gcloud", "compute", "tpus", "tpu-vm", "create", s.name,
               f"--zone={s.zone}",
               f"--accelerator-type={s.accelerator_type}",
               f"--version={s.runtime_version}"]
        if s.project:
            cmd.append(f"--project={s.project}")
        if s.spot:
            cmd.append("--spot")
        return cmd

    def delete_command(self) -> list[str]:
        s = self.spec
        return ["gcloud", "compute", "tpus", "tpu-vm", "delete", s.name,
                f"--zone={s.zone}", "--quiet"]

    # -- per-host bootstrap (HostProvisioner parity) --------------------
    def bootstrap_command(self, repo_url: str,
                          workdir: str = "~/deeplearning4j_tpu") -> str:
        """What ``HostProvisioner`` uploads+runs over SSH: fetch the
        framework and its deps onto every host."""
        return (f"git clone {shlex.quote(repo_url)} {workdir} 2>/dev/null "
                f"|| git -C {workdir} pull && "
                f"pip install -U jax[tpu] flax optax orbax-checkpoint")

    def ssh_all_command(self, remote_cmd: str) -> list[str]:
        s = self.spec
        return ["gcloud", "compute", "tpus", "tpu-vm", "ssh", s.name,
                f"--zone={s.zone}", "--worker=all",
                f"--command={remote_cmd}"]

    # -- coordinated launch (ClusterSetup + jax.distributed contract) ----
    def launch_env(self, process_id: int, coordinator_host: str) -> dict[str, str]:
        """Per-host env for ``initialize_multihost`` (the Akka-seed-join
        replacement): coordinator on host 0, one process per host."""
        s = self.spec
        return {
            "JAX_COORDINATOR_ADDRESS": f"{coordinator_host}:{s.coordinator_port}",
            "JAX_NUM_PROCESSES": str(s.n_hosts),
            "JAX_PROCESS_ID": str(process_id),
        }

    def launch_command(self, train_argv: str, coordinator_host: str,
                       workdir: str = "~/deeplearning4j_tpu") -> str:
        """One command runnable via ``--worker=all``: each host derives its
        process id from the TPU metadata worker index and starts the same
        program (SPMD single-controller-per-host)."""
        s = self.spec
        env = " ".join(
            f"{k}={v}" for k, v in self.launch_env(0, coordinator_host).items()
            if k != "JAX_PROCESS_ID")
        return (f"cd {workdir} && {env} "
                "JAX_PROCESS_ID=$(curl -s -H 'Metadata-Flavor: Google' "
                "'http://metadata/computeMetadata/v1/instance/attributes/"
                "agent-worker-number') "
                f"python {train_argv}")

    # -- execution (ClusterSetup.java:24 actually provisions) ------------

    def describe_ip_command(self) -> list[str]:
        s = self.spec
        return ["gcloud", "compute", "tpus", "tpu-vm", "describe", s.name,
                f"--zone={s.zone}",
                "--format=value(networkEndpoints[0].ipAddress)"]

    def apply(self, repo_url: str, train_argv: str, *, dry_run: bool = True,
              coordinator_host: str | None = None,
              timeout_s: float = 1800.0) -> list[dict]:
        """EXECUTE the provisioning sequence — create the slice, bootstrap
        every host, resolve the coordinator IP, launch everywhere — the way
        the reference's ``ClusterSetup``/``HostProvisioner`` actually SSH
        into boxes rather than printing commands.  ``dry_run`` (the
        default) returns the resolved command list without running
        anything; pass ``dry_run=False`` where a cloud and ``gcloud``
        exist.  Returns one ``{"step", "cmd", "rc", "stdout"}`` record per
        command (``rc`` is None under dry-run); raises on the first
        failing step, since later steps depend on earlier ones.  A step
        that exceeds ``timeout_s`` raises a ``RuntimeError`` naming the
        step with the records-so-far attached as ``err.records`` (a
        half-created slice keeps its audit trail)."""
        records = []

        def run(step: str, cmd: list[str]) -> str:
            rec = {"step": step, "cmd": cmd, "rc": None, "stdout": ""}
            records.append(rec)
            if dry_run:
                return ""
            try:
                proc = subprocess.run(cmd, capture_output=True, text=True,
                                      timeout=timeout_s)
            except subprocess.TimeoutExpired as e:
                # a timed-out create/bootstrap leaves a HALF-CREATED slice
                # behind: name the step and carry the audit trail so the
                # caller can tear down exactly what was attempted
                err = RuntimeError(
                    f"provision step {step!r} timed out after "
                    f"{timeout_s:.0f}s — the slice may be half-created; "
                    "inspect err.records and run teardown()")
                err.records = records
                raise err from e
            rec["rc"] = proc.returncode
            rec["stdout"] = proc.stdout.strip()
            if proc.returncode != 0:
                raise RuntimeError(
                    f"provision step {step!r} failed rc={proc.returncode}: "
                    f"{proc.stderr[-500:]}")
            return rec["stdout"]

        run("create", self.create_command())
        run("bootstrap", self.ssh_all_command(self.bootstrap_command(repo_url)))
        coord = coordinator_host or run("resolve_coordinator",
                                        self.describe_ip_command())
        if not coord:
            if dry_run:
                coord = "$COORD"     # placeholder, as in the rendered script
            else:
                # launching a pod against an empty coordinator address hangs
                # every host in distributed init with no error — fail here
                raise RuntimeError(
                    "coordinator IP resolve returned empty (slice endpoint "
                    "not yet populated?) — refusing to launch")
        run("launch", self.ssh_all_command(
            self.launch_command(train_argv, coord)))
        return records

    def teardown(self, *, dry_run: bool = True,
                 timeout_s: float = 1800.0) -> dict:
        """EXECUTE slice deletion (the Kill-side symmetry of ``apply``)."""
        cmd = self.delete_command()
        rec = {"step": "delete", "cmd": cmd, "rc": None, "stdout": ""}
        if not dry_run:
            try:
                proc = subprocess.run(cmd, capture_output=True, text=True,
                                      timeout=timeout_s)
            except subprocess.TimeoutExpired as e:
                err = RuntimeError(
                    f"teardown step 'delete' timed out after {timeout_s:.0f}s "
                    "— the slice may still exist; inspect err.records")
                err.records = [rec]
                raise err from e
            rec["rc"] = proc.returncode
            rec["stdout"] = proc.stdout.strip()
            if proc.returncode != 0:
                raise RuntimeError(
                    f"teardown failed rc={proc.returncode}: "
                    f"{proc.stderr[-500:]}")
        return rec

    # -- one-file artifact ----------------------------------------------
    def render_script(self, repo_url: str, train_argv: str,
                      coordinator_host: str = "$(gcloud compute tpus tpu-vm "
                      "describe {name} --zone={zone} --format="
                      "'value(networkEndpoints[0].ipAddress)')") -> str:
        s = self.spec
        coord = coordinator_host.format(name=s.name, zone=s.zone)
        lines = [
            "#!/usr/bin/env bash",
            "# Auto-generated pod-slice provisioning script "
            f"({s.accelerator_type}, {s.n_hosts} hosts, {s.n_chips} chips)",
            "set -euo pipefail",
            "",
            "# 1. create the slice",
            shlex.join(self.create_command()),
            "",
            "# 2. bootstrap every host",
            shlex.join(self.ssh_all_command(self.bootstrap_command(repo_url))),
            "",
            "# 3. resolve coordinator (host 0) and launch everywhere",
            f'COORD={coord}',
            # manual quoting: $COORD must expand in the OUTER shell, so the
            # --command payload is double-quoted, not shlex-single-quoted
            # $COORD expands on the operator machine; the $(curl ...) worker-
            # index lookup is escaped so it runs on each TPU host instead
            (shlex.join(self.ssh_all_command("")[:-1])
             + ' "--command=' + self.launch_command(train_argv, "$COORD")
             .replace('"', '\\"').replace("$(curl", "\\$(curl") + '"'),
            "",
        ]
        return "\n".join(lines)

    def write_script(self, path: str | Path, repo_url: str,
                     train_argv: str) -> Path:
        path = Path(path)
        path.write_text(self.render_script(repo_url, train_argv))
        path.chmod(0o755)
        return path
