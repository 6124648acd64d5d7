"""Fault-tolerance tests — SURVEY.md §5's fault-injection tier:
(a) in-process: signal → coordinated checkpoint → stop → resume;
(b) subprocess: kill a real training run mid-flight, restart, assert resume.
"""

import os
import signal
import subprocess
import sys
import time

import jax
import numpy as np
import optax
import pytest

from tests.helpers import free_ports

from distributed_tensorflow_tpu.checkpoint import CheckpointManager
from distributed_tensorflow_tpu.ft import (
    HealthChecker,
    PreemptionCheckpointHook,
    PreemptionWatcher,
    TerminationConfig,
)
from distributed_tensorflow_tpu.training import FP32, TrainLoop, make_train_step
from distributed_tensorflow_tpu.training.loop import Hook
from tests.test_training import linear_batch, make_linear_state, quadratic_loss


class TestPreemptionWatcher:
    def test_real_signal_sets_flag(self):
        w = PreemptionWatcher(TerminationConfig(signals=(signal.SIGUSR1,)))
        w.install()
        try:
            assert not w.preempted
            os.kill(os.getpid(), signal.SIGUSR1)
            time.sleep(0.05)
            assert w.preempted
        finally:
            w.uninstall()

    def test_env_config(self, monkeypatch):
        monkeypatch.setenv("DTT_PREEMPTION_SIGNALS", "SIGUSR2,SIGTERM")
        monkeypatch.setenv("DTT_GRACE_PERIOD_S", "7.5")
        cfg = TerminationConfig.from_env()
        assert signal.SIGUSR2 in cfg.signals and signal.SIGTERM in cfg.signals
        assert cfg.grace_period_s == 7.5


class TestPreemptionCheckpointHook:
    def test_preemption_saves_and_stops_then_resumes(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), save_interval_steps=1,
                                async_save=False)
        watcher = PreemptionWatcher(TerminationConfig(signals=()))
        hook = PreemptionCheckpointHook(mgr, watcher, sync_every=5)

        state = make_linear_state()
        step = make_train_step(quadratic_loss, precision=FP32)
        data = iter(lambda: linear_batch(), None)

        class TriggerAt(Hook):
            def after_step(self, loop, s, m):
                if s == 7:
                    watcher.signal_preemption()

        loop = TrainLoop(step, state, data,
                         hooks=[TriggerAt(), hook], metrics_every=1)
        final = loop.run(100)
        stopped_at = int(jax.device_get(final.step))
        assert stopped_at == 10  # next sync point after step 7
        assert hook.handled
        assert mgr.latest_step() == 10

        # restart: resume from the preemption checkpoint
        state2 = make_linear_state()
        restored = mgr.restore_or_init(state2)
        assert int(jax.device_get(restored.step)) == 10
        mgr.close()


PSM_SCRIPT = r"""
import os, sys, time
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np

from distributed_tensorflow_tpu import cluster as cluster_lib
from distributed_tensorflow_tpu.checkpoint import CheckpointManager
from distributed_tensorflow_tpu.ft import (
    PreemptionCheckpointHook, PreemptionWatcher, TerminationConfig,
)
from distributed_tensorflow_tpu.training import FP32, TrainLoop, make_train_step
from tests.test_training import linear_batch, make_linear_state, quadratic_loss

resolver = cluster_lib.resolve()
server = cluster_lib.Server.from_resolver(resolver)
assert jax.process_count() == 2


class RecordingManager:
    # The real orbax save path is covered elsewhere (multihost save of a
    # process-local test state is an orbax no-go); THIS test asserts the
    # notice propagation + step agreement.
    def __init__(self):
        self.saved = []

    def save(self, step, state, force=False):
        self.saved.append(step)

    def wait_until_finished(self):
        pass


mgr = RecordingManager()
# Watcher listens to NO signals: SIGTERM must flow through the JAX
# preemption sync manager (the platform-notice path under test).
watcher = PreemptionWatcher(TerminationConfig(signals=())).install()
hook = PreemptionCheckpointHook(mgr, watcher, sync_every=10_000)

state = make_linear_state()
step = make_train_step(quadratic_loss, precision=FP32)
marker = os.path.join(sys.argv[1], f"training{jax.process_index()}")


class Slow:
    def __init__(self):
        self.n = 0

    def __iter__(self):
        return self

    def __next__(self):
        self.n += 1
        if self.n == 30:  # both workers well into training -> safe to signal
            # ... and the runtime's notifier is up: the hook has by now
            # consulted the sync manager 29 times and was never refused.
            from distributed_tensorflow_tpu.ft import preemption
            assert not preemption._PSM_UNAVAILABLE_LOGGED
            open(marker, "w").close()
        time.sleep(0.05)
        return linear_batch()


print("PSM_TRAIN_READY", flush=True)
loop = TrainLoop(step, state, Slow(), hooks=[hook], metrics_every=1)
final = loop.run(2000)
stopped = int(jax.device_get(final.step))
assert hook.handled, "hook never saw the platform preemption notice"
assert mgr.saved and mgr.saved[-1] == stopped
print("PSM_STOPPED_AT", stopped, flush=True)
from tests.helpers import leave_in_order
leave_in_order()
"""



def test_platform_preemption_notice_stops_both_workers(tmp_path):
    """SIGTERM to ONE worker propagates through JAX's preemption sync
    manager (not our signal watcher — it listens to no signals here) and
    both workers checkpoint and stop at the SAME agreed step (SURVEY.md
    §6.3 platform-notice path; VERDICT missing #6)."""
    import json

    p0, p1 = free_ports(2)
    cluster = {"worker": [f"localhost:{p0}", f"localhost:{p1}"]}
    procs = []
    for idx in range(2):
        env = dict(
            os.environ,
            TF_CONFIG=json.dumps(
                {"cluster": cluster, "task": {"type": "worker", "index": idx}}
            ),
            JAX_PLATFORMS="cpu",
        )
        procs.append(subprocess.Popen(
            [sys.executable, "-c", PSM_SCRIPT, str(tmp_path)],
            env=env, cwd=os.path.dirname(os.path.dirname(__file__)),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        ))
    try:
        deadline = time.time() + 120
        # wait until BOTH workers are ~30 steps into training (marker files,
        # written only where the runtime's preemption notifier is up: a
        # signal before that is lost) before delivering the notice.
        while time.time() < deadline:
            if all(os.path.exists(os.path.join(str(tmp_path), f"training{i}"))
                   for i in range(2)):
                break
            time.sleep(0.5)
        else:
            for q in procs:
                q.kill()
            pytest.fail("workers never reached training")
        procs[1].send_signal(signal.SIGTERM)  # scheduler preempts worker 1
        outs = []
        for p in procs:
            out, _ = p.communicate(timeout=300)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for q in procs:
            q.kill()
        pytest.fail("workers hung after platform preemption notice")
    steps = []
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {i}:\n{out[-4000:]}"
        assert "PSM_STOPPED_AT" in out, out[-2000:]
        steps.append(int(out.split("PSM_STOPPED_AT")[1].split()[0]))
    assert steps[0] == steps[1], f"workers stopped at different steps {steps}"


class TestHealthChecker:
    def test_failure_after_consecutive_probes(self):
        calls = []
        hc = HealthChecker(
            interval_s=0.01, failures_before_action=2,
            probe=lambda t: False, on_failure=lambda: calls.append(1),
        )
        hc.mark_ready()  # post-startup regime: failures count directly
        hc.start()
        deadline = time.time() + 5
        while hc.error is None and time.time() < deadline:
            time.sleep(0.01)
        hc.stop()
        assert hc.error is not None
        assert calls == [1]
        with pytest.raises(RuntimeError):
            hc.raise_if_unhealthy()

    def test_startup_grace_tolerates_then_raises(self):
        """ADVICE r2: probes armed from loop begin must tolerate failed
        probes during startup (peer still compiling) but still surface a
        peer that NEVER comes up once the grace window is exhausted."""
        hc = HealthChecker(
            interval_s=0.01, failures_before_action=1,
            startup_grace_s=0.3, probe=lambda t: False,
        )
        hc.start()
        time.sleep(0.1)
        assert hc.error is None  # inside the grace window
        deadline = time.time() + 5
        while hc.error is None and time.time() < deadline:
            time.sleep(0.01)
        hc.stop()
        assert hc.error is not None  # grace exhausted -> raise

    def test_mark_ready_ends_grace_immediately(self):
        hc = HealthChecker(
            interval_s=0.01, failures_before_action=2,
            startup_grace_s=3600.0, probe=lambda t: False,
        )
        hc.mark_ready()  # first step completed: normal thresholds apply
        hc.start()
        deadline = time.time() + 5
        while hc.error is None and time.time() < deadline:
            time.sleep(0.01)
        hc.stop()
        assert hc.error is not None

    def test_recovery_resets_counter(self):
        results = iter([False, True, False, True, True])
        hc = HealthChecker(
            interval_s=0.01, failures_before_action=2,
            probe=lambda t: next(results, True),
        )
        hc.start()
        time.sleep(0.3)
        hc.stop()
        assert hc.error is None
        hc.raise_if_unhealthy()  # no raise


SUBPROC_SCRIPT = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
from distributed_tensorflow_tpu.train_lib import TrainArgs, run

args = TrainArgs(
    model="mnist", steps=100000, batch_size=32,
    checkpoint_dir=sys.argv[1], checkpoint_every=20, log_every=10,
)
run(args)
"""


class TestKillAWorker:
    def test_sigterm_mid_training_checkpoints_and_resumes(self, tmp_path):
        """Fault injection: real process, real SIGTERM, real resume."""
        ckpt = str(tmp_path / "ckpt")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.Popen(
            [sys.executable, "-c", SUBPROC_SCRIPT, ckpt],
            env=env, cwd=os.path.dirname(os.path.dirname(__file__)),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        # signal once it has compiled and passed two checkpoint intervals
        deadline = time.time() + 120
        while not (os.path.isdir(ckpt) and any(
                d.isdigit() and int(d) >= 40 for d in os.listdir(ckpt))):
            if proc.poll() is not None or time.time() > deadline:
                proc.kill()
                pytest.fail("no second checkpoint within 120 s; output:\n"
                            + proc.communicate()[0][-3000:])
            time.sleep(0.5)
        proc.send_signal(signal.SIGTERM)
        try:
            out, _ = proc.communicate(timeout=120)
        except subprocess.TimeoutExpired:
            proc.kill()
            out, _ = proc.communicate()
            pytest.fail(f"worker did not exit after SIGTERM; output:\n{out[-3000:]}")
        assert "preemption" in out.lower(), out[-3000:]

        steps = sorted(
            int(d) for d in os.listdir(ckpt) if d.isdigit()
        ) if os.path.isdir(ckpt) else []
        assert steps, f"no checkpoint written; output:\n{out[-3000:]}"

        # restart: must resume from the saved step, not step 0
        env2 = dict(env)
        proc2 = subprocess.run(
            [sys.executable, "-c", SUBPROC_SCRIPT.replace("100000",
             str(steps[-1] + 5)), ckpt],
            env=env2, cwd=os.path.dirname(os.path.dirname(__file__)),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            timeout=300,
        )
        assert f"resumed from checkpoint step {steps[-1]}" in proc2.stdout, (
            proc2.stdout[-3000:]
        )


class TestProbeIsolationWrapper:
    """VERDICT r3 #9: a JAX upgrade that moves the private distributed
    surface must RAISE at probe construction in multi-process runs, not
    silently report healthy forever."""

    def test_moved_internals_raise_loudly(self, monkeypatch):
        import jax as _jax

        from distributed_tensorflow_tpu.ft import BarrierUnavailableError
        from distributed_tensorflow_tpu.ft.health import make_default_probe

        monkeypatch.setattr(_jax, "process_count", lambda: 2)

        class MovedState:  # no .client attribute -> AttributeError
            pass

        monkeypatch.setattr(_jax._src.distributed, "global_state",
                            MovedState())
        with pytest.raises(BarrierUnavailableError, match="moved"):
            make_default_probe(1.0)

    def test_uninitialized_client_raises(self, monkeypatch):
        import jax as _jax

        from distributed_tensorflow_tpu.ft import BarrierUnavailableError
        from distributed_tensorflow_tpu.ft.health import make_default_probe

        monkeypatch.setattr(_jax, "process_count", lambda: 2)

        class State:
            client = None

        monkeypatch.setattr(_jax._src.distributed, "global_state", State())
        with pytest.raises(BarrierUnavailableError, match="not initialized"):
            make_default_probe(1.0)

    def test_single_process_probe_is_trivially_healthy(self):
        from distributed_tensorflow_tpu.ft.health import make_default_probe

        assert make_default_probe(1.0)(0.1) is True
