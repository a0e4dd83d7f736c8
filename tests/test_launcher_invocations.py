"""Invocation-shape tests for every cluster launcher (VERDICT r3 item 7).

Each launcher's job is to turn (args, tracker envs) into the EXACT external
command its scheduler expects — qsub/srun/mpirun/ssh/mesos-execute/yarn/
kubectl.  These tests monkeypatch the subprocess layer and the submit()
rendezvous (covered by its own tests) and assert the command and env
contract per launcher, the part no other test observes.

The reference ships these launchers untested; asserting the command shape
is the cheapest meaningful upgrade over that floor.
"""
import json
import os
from pathlib import Path

import pytest

from dmlc_core_tpu.tracker.opts import parse

ENVS = {"DMLC_TRACKER_URI": "10.0.0.9", "DMLC_TRACKER_PORT": 9091,
        "DMLC_NUM_WORKER": 2, "DMLC_NUM_SERVER": 0}


class FakeTracker:
    def __init__(self):
        self.stopped = False

    def alive(self):
        return False

    def join(self, timeout=None):
        pass

    def stop(self):
        self.stopped = True


class FakeProc:
    returncode = 0

    def poll(self):
        return 0

    def wait(self):
        return 0


def fake_submit(calls):
    """A submit() stand-in: hands launchers a fixed env contract."""
    def submit(num_workers, num_servers, fun_submit, **kw):
        envs = dict(ENVS)
        envs["DMLC_NUM_WORKER"] = num_workers
        envs["DMLC_NUM_SERVER"] = num_servers
        fun_submit(num_workers, num_servers, envs)
        return FakeTracker()
    return submit


def capture_run(calls):
    def run(cmd, **kw):
        calls.append({"cmd": cmd, **{k: kw[k] for k in ("env", "input")
                                     if k in kw}})
        return FakeProc()
    return run


def test_ssh_command_shape(monkeypatch, tmp_path):
    from dmlc_core_tpu.tracker.launchers import ssh
    hosts = tmp_path / "hosts"
    hosts.write_text("nodeA:2222 slots=4\nnodeB  # comment\n")
    calls = []
    monkeypatch.setattr(ssh, "submit", fake_submit(calls))
    monkeypatch.setattr(ssh.subprocess, "run", capture_run(calls))
    args = parse(["--cluster=ssh", "-n", "2", "-H", str(hosts),
                  "--", "python", "train.py"])
    ssh.run(args)
    assert len(calls) == 2
    # rank threads launch concurrently, so capture order is scheduler-
    # dependent (the tpu test below hit the same race under load): key the
    # assertions on the target host, never on list position.
    by_host = {c["cmd"][5]: c["cmd"] for c in calls}
    assert sorted(by_host) == ["nodeA", "nodeB"]
    cA = by_host["nodeA"]
    assert cA[:5] == ["ssh", "-o", "StrictHostKeyChecking=no", "-p", "2222"]
    remote = cA[6]
    assert "export DMLC_ROLE=worker" in remote
    assert "export DMLC_TASK_ID=0" in remote
    assert "export DMLC_TRACKER_URI=10.0.0.9" in remote
    assert "export DMLC_JOB_CLUSTER=ssh" in remote
    assert remote.endswith("python train.py")
    # second rank wraps to nodeB on the default port
    cB = by_host["nodeB"]
    assert cB[3:6] == ["-p", "22", "nodeB"]
    assert "export DMLC_TASK_ID=1" in cB[6]


def test_tpu_localhost_and_remote_shape(monkeypatch, tmp_path):
    from dmlc_core_tpu.tracker.launchers import tpu
    calls = []
    monkeypatch.setattr(tpu, "submit", fake_submit(calls))
    monkeypatch.setattr(tpu.subprocess, "run", capture_run(calls))
    # localhost slice: direct exec with TPU_WORKER_ID in env
    args = parse(["--cluster=tpu", "-n", "1", "--", "python", "step.py"])
    monkeypatch.delenv("TPU_WORKER_HOSTNAMES", raising=False)
    tpu.run(args)
    assert calls[0]["cmd"] == ["python", "step.py"]
    env = calls[0]["env"]
    assert env["TPU_WORKER_ID"] == "0" and env["DMLC_ROLE"] == "worker"
    assert env["DMLC_JOB_CLUSTER"] == "tpu"
    # slice hosts from env: ssh with exports, topology order = worker id
    calls.clear()
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "tpu-w0,tpu-w1")
    args = parse(["--cluster=tpu", "-n", "2", "--", "python", "step.py"])
    tpu.run(args)
    # rank threads launch concurrently, so capture order is nondeterministic
    # (observed flipping under full-suite load): assert by host, not index
    by_host = {c["cmd"][5]: c for c in calls}
    assert len(calls) == 2  # exactly one launch per worker (no dup collapse)
    assert sorted(by_host) == ["tpu-w0", "tpu-w1"]
    assert "export TPU_WORKER_ID=1" in by_host["tpu-w1"]["cmd"][6]
    assert "export TPU_WORKER_ID=0" in by_host["tpu-w0"]["cmd"][6]
    # more ranks than hosts would put two processes on one host's chips
    calls.clear()
    args = parse(["--cluster=tpu", "-n", "3", "--", "python", "step.py"])
    with pytest.raises(SystemExit, match="one rank per TPU host"):
        tpu.run(args)
    assert not calls


@pytest.mark.parametrize("flavor,version_text", [
    ("openmpi", "mpirun (Open MPI) 4.1.4"),
    ("mpich", "HYDRA build details: mpich version 4.0"),
])
def test_mpi_command_shape(monkeypatch, flavor, version_text):
    from dmlc_core_tpu.tracker.launchers import mpi
    calls = []

    def fake_run(cmd, **kw):
        assert cmd == ["mpirun", "--version"]

        class Out:
            stdout = version_text
        return Out()

    monkeypatch.setattr(mpi.subprocess, "run", fake_run)
    monkeypatch.setattr(mpi.subprocess, "Popen",
                        lambda cmd, **kw: calls.append(cmd) or FakeProc())
    monkeypatch.setattr(mpi, "submit", fake_submit(calls))
    args = parse(["--cluster=mpi", "-n", "3", "--", "python", "train.py"])
    mpi.run(args)
    (cmd,) = calls
    assert cmd[:3] == ["mpirun", "-n", "3"]
    if flavor == "openmpi":
        assert "-x" in cmd and "DMLC_ROLE=worker" in cmd
        assert f"DMLC_TRACKER_URI={ENVS['DMLC_TRACKER_URI']}" in cmd
    else:
        i = cmd.index("DMLC_ROLE")
        assert cmd[i - 1] == "-env" and cmd[i + 1] == "worker"
    assert cmd[-2:] == ["python", "train.py"]


def test_slurm_command_shape(monkeypatch):
    from dmlc_core_tpu.tracker.launchers import slurm
    calls = []
    monkeypatch.setattr(slurm, "submit", fake_submit(calls))
    monkeypatch.setattr(slurm.subprocess, "Popen",
                        lambda cmd, **kw: calls.append(cmd) or FakeProc())
    args = parse(["--cluster=slurm", "-n", "4", "--jobname", "exp1",
                  "--", "python", "train.py"])
    slurm.run(args)
    (cmd,) = calls
    assert cmd[0] == "srun" and "--ntasks=4" in cmd
    export = next(a for a in cmd if a.startswith("--export="))
    assert export.startswith("--export=ALL,")
    assert "DMLC_ROLE=worker" in export and "DMLC_JOB_CLUSTER=slurm" in export
    assert "--job-name=exp1-worker" in cmd
    assert cmd[-2:] == ["python", "train.py"]


def test_sge_qsub_and_wrapper_shape(monkeypatch):
    from dmlc_core_tpu.tracker.launchers import sge
    calls = []
    monkeypatch.setattr(sge, "submit", fake_submit(calls))
    monkeypatch.setattr(sge.subprocess, "run", capture_run(calls))
    args = parse(["--cluster=sge", "-n", "5", "--jobname", "grid",
                  "--", "python", "train.py"])
    sge.run(args)
    (call,) = calls
    cmd = call["cmd"]
    assert cmd[:5] == ["qsub", "-cwd", "-t", "1-5", "-N"]
    assert cmd[5] == "grid-worker"
    wrapper = Path(cmd[6]).read_text()
    assert "export DMLC_ROLE=worker" in wrapper
    assert "export DMLC_TASK_ID=$((SGE_TASK_ID - 1))" in wrapper
    assert "export DMLC_TRACKER_PORT=9091" in wrapper
    assert wrapper.rstrip().endswith("python train.py")


def test_mesos_command_shape(monkeypatch):
    from dmlc_core_tpu.tracker.launchers import mesos
    calls = []
    monkeypatch.setattr(mesos.shutil, "which", lambda _: "/usr/bin/mesos-execute")
    monkeypatch.setattr(mesos, "submit", fake_submit(calls))
    monkeypatch.setattr(mesos.subprocess, "run", capture_run(calls))
    args = parse(["--cluster=mesos", "-n", "1", "--worker-cores", "2",
                  "--worker-memory-mb", "2048", "--env",
                  "MESOS_MASTER=zk://zk1/mesos", "--", "python", "train.py"])
    mesos.run(args)
    # threads: wait for the spawned rank thread to record its call
    import time
    for _ in range(50):
        if calls:
            break
        time.sleep(0.1)
    cmd = calls[0]["cmd"]
    assert cmd[0] == "mesos-execute"
    assert "--master=zk://zk1/mesos" in cmd
    assert "--name=dmlc-worker-0" in cmd
    assert "--resources=cpus:2;mem:2048" in cmd
    env_json = json.loads(next(a for a in cmd if a.startswith("--env="))[len("--env="):])
    names = {v["name"]: v["value"] for v in env_json["variables"]}
    assert names["DMLC_ROLE"] == "worker" and names["DMLC_TASK_ID"] == "0"
    assert cmd[-1] == "--command=python train.py"


def test_yarn_command_shape(monkeypatch):
    from dmlc_core_tpu.tracker.launchers import yarn
    calls = []
    monkeypatch.setattr(yarn.shutil, "which", lambda _: "/usr/bin/yarn")
    monkeypatch.setattr(yarn, "submit", fake_submit(calls))
    monkeypatch.setattr(yarn.subprocess, "Popen",
                        lambda cmd, **kw: calls.append(cmd) or FakeProc())
    monkeypatch.setenv("HADOOP_YARN_DS_JAR", "/opt/ds.jar")
    args = parse(["--cluster=yarn", "-n", "6", "--queue", "prod",
                  "--container-retries", "5", "--", "python", "train.py"])
    yarn.run(args)
    (cmd,) = calls
    assert cmd[:3] == ["yarn", "jar", "/opt/ds.jar"]
    i = cmd.index("-num_containers")
    assert cmd[i + 1] == "6"
    assert cmd[cmd.index("-queue") + 1] == "prod"
    assert cmd[cmd.index("-container_retry_policy") + 1] == "RETRY_ON_ALL_ERRORS"
    assert cmd[cmd.index("-container_max_retries") + 1] == "5"
    shell_env = cmd[cmd.index("-shell_env") + 1]
    assert "DMLC_ROLE=worker" in shell_env and "DMLC_TRACKER_URI=10.0.0.9" in shell_env
    assert cmd[cmd.index("-shell_command") + 1] == "python train.py"


def _fake_yarn_cli(tmp_path, monkeypatch, fail_first_n):
    """Install a fake `yarn` CLI on PATH.  `yarn jar` submissions are
    logged to the returned file and the first ``fail_first_n`` of them
    fail (-1 = fail always); `yarn application` calls are logged to the
    sibling `appcalls` file, with -list reporting one RUNNING app named
    dmlc-worker (so the stale-app sweep has something to kill)."""
    bindir = tmp_path / "bin"
    bindir.mkdir()
    count = tmp_path / "invocations"
    count.write_text("")
    appcalls = tmp_path / "appcalls"
    appcalls.write_text("")
    script = bindir / "yarn"
    if fail_first_n < 0:
        body = "exit 1\n"
    else:
        body = (f'if [ "$(wc -l < "{count}")" -le {fail_first_n} ]; '
                "then exit 1; else exit 0; fi\n")
    # -list echoes back the appname recorded from the last submission, so
    # the sweep-by-name assertions track the launcher's unique job tag
    name_file = tmp_path / "last_appname"
    script.write_text(f'''#!/bin/sh
if [ "$1" = "application" ]; then
  echo "$@" >> "{appcalls}"
  case "$*" in
    *-list*) printf 'application_1_0001\\t%s\\tDISTRIBUTEDSHELL\\n' \
        "$(cat "{name_file}" 2>/dev/null)";;
  esac
  exit 0
fi
all="$*"
prev=""
for a in "$@"; do
  if [ "$prev" = "-appname" ]; then echo "$a" > "{name_file}"; fi
  prev="$a"
done
echo "$all" >> "{count}"
{body}''')
    script.chmod(0o755)
    monkeypatch.setenv("PATH", f"{bindir}:{os.environ['PATH']}")
    monkeypatch.setenv("HADOOP_YARN_DS_JAR", "/opt/ds.jar")
    return count


class ConditionTracker(FakeTracker):
    """alive() until ``done()`` holds (or a generous poll cap, so a
    regression fails the test instead of hanging it).  Condition-driven,
    not time-driven: the resubmit loop's progress is scheduler-dependent,
    and a fixed countdown would race it under load."""

    def __init__(self, done, cap=6000):
        super().__init__()
        self.done, self.cap = done, cap

    def alive(self):
        self.cap -= 1
        return self.cap > 0 and not self.done()


def _yarn_submit(tracker):
    def submit(num_workers, num_servers, fun_submit, **kw):
        envs = dict(ENVS)
        envs["DMLC_NUM_WORKER"] = num_workers
        envs["DMLC_NUM_SERVER"] = num_servers
        fun_submit(num_workers, num_servers, envs)
        return tracker
    return submit


def test_yarn_resubmits_failed_application(monkeypatch, tmp_path):
    """Reference-AM restart parity: a failed application (its `yarn jar`
    client exits non-zero) is resubmitted by OUR launcher code, and the
    job succeeds once the resubmission does."""
    from dmlc_core_tpu.tracker.launchers import yarn
    count = _fake_yarn_cli(tmp_path, monkeypatch, fail_first_n=1)
    monkeypatch.setattr(yarn, "_POLL_S", 0.01)
    # the tracker stays alive until the resubmission is observable, then
    # run() falls through to the final wait on the (succeeding) client
    resubmitted = lambda: len(count.read_text().splitlines()) >= 2  # noqa: E731
    monkeypatch.setattr(yarn, "submit",
                        _yarn_submit(ConditionTracker(resubmitted)))
    args = parse(["--cluster=yarn", "-n", "2", "--", "python", "train.py"])
    yarn.run(args)  # must NOT raise: attempt 2 succeeded
    invocations = count.read_text().strip().splitlines()
    assert len(invocations) == 2  # original + one resubmission
    assert all("-num_containers 2" in line for line in invocations)
    # before resubmitting, the launcher must sweep for a still-live app
    # from the dead client (never two applications' containers per role)
    appcalls = (count.parent / "appcalls").read_text()
    assert "-list" in appcalls
    assert "-kill application_1_0001" in appcalls


def test_yarn_gives_up_after_max_attempts(monkeypatch, tmp_path):
    """DMLC_MAX_ATTEMPT bounds the resubmission loop (the reference AM's
    maxNumAttempt): a persistently failing application kills the job
    after exactly that many submissions."""
    from dmlc_core_tpu.tracker.launchers import yarn
    count = _fake_yarn_cli(tmp_path, monkeypatch, fail_first_n=-1)
    monkeypatch.setattr(yarn, "_POLL_S", 0.01)
    monkeypatch.setattr(yarn, "submit",
                        _yarn_submit(ConditionTracker(lambda: False)))
    monkeypatch.setenv("DMLC_MAX_ATTEMPT", "2")
    args = parse(["--cluster=yarn", "-n", "1", "--", "python", "train.py"])
    with pytest.raises(SystemExit, match="after 2 attempt"):
        yarn.run(args)
    assert len(count.read_text().strip().splitlines()) == 2


def test_kubernetes_manifest_shape(monkeypatch):
    from dmlc_core_tpu.tracker.launchers import kubernetes as k8s
    calls = []
    monkeypatch.setattr(k8s.shutil, "which", lambda _: "/usr/bin/kubectl")
    monkeypatch.setattr(k8s, "submit", fake_submit(calls))
    monkeypatch.setattr(k8s.subprocess, "run", capture_run(calls))
    args = parse(["--cluster=kubernetes", "-n", "3", "--jobname", "kjob",
                  "--container-retries", "2",
                  "--env", "DMLC_K8S_IMAGE=myrepo/train:1",
                  "--", "python", "train.py"])
    k8s.run(args)
    (call,) = calls
    assert call["cmd"] == ["kubectl", "apply", "-f", "-"]
    manifest = json.loads(call["input"])
    assert manifest["kind"] == "Job"
    assert manifest["metadata"]["name"] == "kjob-worker"
    spec = manifest["spec"]
    assert spec["completions"] == 3 and spec["parallelism"] == 3
    assert spec["completionMode"] == "Indexed"
    assert spec["backoffLimitPerIndex"] == 2
    container = spec["template"]["spec"]["containers"][0]
    assert container["image"] == "myrepo/train:1"
    assert container["command"] == ["python", "train.py"]
    env = {e["name"]: e for e in container["env"]}
    assert env["DMLC_ROLE"]["value"] == "worker"
    assert "valueFrom" in env["DMLC_TASK_ID"]  # from job-completion-index
    assert container["resources"]["requests"]["memory"] == "1024Mi"
