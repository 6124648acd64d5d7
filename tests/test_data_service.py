"""Out-of-process input service tests (the tf.data-service role,
SURVEY.md §3.4 / VERDICT missing #2): one server process owns the record
file + native loader; trainers pull disjoint batches over TCP.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from distributed_tensorflow_tpu.data.records import (
    record_path,
    record_schema,
    stage_synthetic_to_records,
)
from distributed_tensorflow_tpu.data.service import (
    DataServiceIterator,
    DataServiceServer,
)
from distributed_tensorflow_tpu.models import get_workload
from distributed_tensorflow_tpu.native import RecordFile
from tests.helpers import free_port

REPO = os.path.dirname(os.path.dirname(__file__))


@pytest.fixture
def indexed_record(tmp_path):
    """64 records whose 'label' field encodes the record index."""
    rec = RecordFile([("x", (4,), np.float32), ("label", (), np.int32)])
    n = 64
    rng = np.random.RandomState(0)
    arrays = {
        "x": rng.randn(n, 4).astype(np.float32),
        "label": np.arange(n, dtype=np.int32),
    }
    path = str(tmp_path / "idx.rec")
    rec.write(path, arrays)
    return path, rec, arrays


class TestDataService:
    def test_round_trip(self, indexed_record):
        path, rec, arrays = indexed_record
        server = DataServiceServer(path, rec, batch_size=8,
                                   shuffle=False, num_threads=1).start()
        try:
            it = DataServiceIterator(server.target, rec, 8)
            b = next(it)
            np.testing.assert_array_equal(b["label"], np.arange(8))
            np.testing.assert_allclose(b["x"], arrays["x"][:8])
            it.close()
        finally:
            server.stop()

    def test_consumers_split_one_stream(self, indexed_record):
        """Two consumers never see the same batch (distributed_epoch
        semantics): one epoch of batches is partitioned across them."""
        path, rec, _ = indexed_record
        # num_threads=1: multi-thread producers can push batches out of
        # epoch-draw order, which would make the strict one-epoch
        # disjointness below racy; stream-splitting is what's under test.
        server = DataServiceServer(path, rec, batch_size=16,
                                   shuffle=True, num_threads=1).start()
        try:
            a = DataServiceIterator(server.target, rec, 16)
            b = DataServiceIterator(server.target, rec, 16)
            labels_a, labels_b = [], []
            for _ in range(2):  # 4 batches total = 64 records = 1 epoch
                labels_a.extend(next(a)["label"].tolist())
                labels_b.extend(next(b)["label"].tolist())
            # within one epoch window the two consumers are disjoint
            assert set(labels_a) | set(labels_b) == set(range(64))
            assert not set(labels_a) & set(labels_b)
            a.close()
            b.close()
        finally:
            server.stop()

    def test_handshake_rejects_schema_mismatch(self, indexed_record):
        path, rec, _ = indexed_record
        server = DataServiceServer(path, rec, batch_size=8).start()
        try:
            wrong = RecordFile([("x", (8,), np.float32)])
            with pytest.raises(ValueError, match="record"):
                DataServiceIterator(server.target, wrong, 8)
            with pytest.raises(ValueError, match="batch"):
                DataServiceIterator(server.target, rec, 4)
        finally:
            server.stop()

    def test_train_from_service(self, tmp_path):
        """train_lib's --data_service path: mnist trains from an in-process
        server thread end to end."""
        from distributed_tensorflow_tpu.train_lib import TrainArgs, run

        wl = get_workload("mnist", batch_size=32)
        path = record_path(str(tmp_path), "mnist")
        stage_synthetic_to_records(wl, path, 256)
        server = DataServiceServer(
            path, record_schema(wl), batch_size=32
        ).start()
        try:
            result = run(TrainArgs(
                model="mnist", steps=10, batch_size=32, log_every=5,
                data_service=server.target,
            ))
            assert result["final_step"] == 10
            assert np.isfinite(result["loss"])
        finally:
            server.stop()

    def test_mid_stream_death_raises_clear_error(self, tmp_path):
        """VERDICT weak #5: a server that DIES mid-stream (no clean
        end-of-stream frame) must surface as DataServiceError naming the
        service address — not a bare ConnectionError, and NOT a silent
        StopIteration the trainer would mistake for epoch end."""
        from distributed_tensorflow_tpu.data.service import DataServiceError

        wl = get_workload("mnist", batch_size=32)
        path = record_path(str(tmp_path), "mnist")
        stage_synthetic_to_records(wl, path, 64)

        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.Popen(
            [sys.executable, "-m", "distributed_tensorflow_tpu.data.service",
             "--model=mnist", f"--data_dir={tmp_path}", "--batch_size=32"],
            env=env, cwd=REPO, stdout=subprocess.PIPE, text=True,
        )
        try:
            line = proc.stdout.readline()
            assert line.startswith("DATA_SERVICE_READY"), line
            target = line.split()[1]
            it = DataServiceIterator(target, record_schema(wl), 32)
            next(it)  # stream is live
            proc.kill()  # hard death: no clean 0-length frame
            proc.wait(timeout=30)
            with pytest.raises(DataServiceError, match=target.split(":")[0]):
                for _ in range(10_000):  # buffered batches may drain first
                    next(it)
            it.close()  # close after death must not raise
        finally:
            proc.kill()
            proc.wait(timeout=30)

    def test_dispatcher_workers_cover_one_epoch(self, indexed_record):
        """Dispatcher tier: two workers each own half the record stripes;
        a round-robin client sees the whole epoch exactly once."""
        from distributed_tensorflow_tpu.data.dispatcher import (
            DataServiceDispatcher,
            DistributedDataServiceIterator,
            register_worker,
        )

        path, rec, _ = indexed_record
        disp = DataServiceDispatcher().start()
        workers = [
            DataServiceServer(path, rec, batch_size=8, shuffle=False,
                              num_threads=1, shard_index=i,
                              shard_count=2).start()
            for i in range(2)
        ]
        try:
            for w in workers:
                register_worker(disp.target, w.target)
            it = DistributedDataServiceIterator(disp.target, rec, 8)
            labels = []
            for _ in range(8):  # 64 records / batch 8 = one epoch
                labels.extend(next(it)["label"].tolist())
            assert sorted(labels) == list(range(64))
            it.close()
        finally:
            for w in workers:
                w.stop()
            disp.stop()

    def test_dispatcher_survives_worker_death(self, tmp_path):
        """One worker is SIGKILLed mid-stream: the client drops it with a
        warning and keeps pulling from the survivor; training never sees
        an error (tf.data-service worker-restart semantics, minus the
        lost shard's remaining records)."""
        from distributed_tensorflow_tpu.data.dispatcher import (
            DataServiceDispatcher,
            DistributedDataServiceIterator,
            register_worker,
        )

        wl = get_workload("mnist", batch_size=32)
        path = record_path(str(tmp_path), "mnist")
        stage_synthetic_to_records(wl, path, 512)
        rec = record_schema(wl)

        disp = DataServiceDispatcher().start()
        survivor = DataServiceServer(path, rec, batch_size=32,
                                     shard_index=0, shard_count=2).start()
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        doomed = subprocess.Popen(
            [sys.executable, "-m", "distributed_tensorflow_tpu.data.service",
             "--model=mnist", f"--data_dir={tmp_path}", "--batch_size=32",
             "--shard_index=1", "--shard_count=2"],
            env=env, cwd=REPO, stdout=subprocess.PIPE, text=True,
        )
        try:
            line = doomed.stdout.readline()
            assert line.startswith("DATA_SERVICE_READY"), line
            register_worker(disp.target, survivor.target)
            register_worker(disp.target, line.split()[1])

            it = DistributedDataServiceIterator(disp.target, rec, 32)
            next(it)  # both live
            doomed.kill()
            doomed.wait(timeout=30)
            # keep pulling well past any buffered batches: the stream must
            # continue from the survivor, not raise
            for _ in range(6):
                b = next(it)
                assert b["image"].shape[0] == 32
            it.close()
        finally:
            doomed.kill()
            doomed.wait(timeout=30)
            survivor.stop()
            disp.stop()

    def test_train_from_dispatcher(self, tmp_path):
        """train_lib's --data_service=dispatch://... path end to end: mnist
        trains from a 2-worker dispatcher fleet."""
        from distributed_tensorflow_tpu.data.dispatcher import (
            DataServiceDispatcher,
            register_worker,
        )
        from distributed_tensorflow_tpu.train_lib import TrainArgs, run

        wl = get_workload("mnist", batch_size=32)
        path = record_path(str(tmp_path), "mnist")
        stage_synthetic_to_records(wl, path, 512)
        rec = record_schema(wl)

        disp = DataServiceDispatcher().start()
        workers = [
            DataServiceServer(path, rec, batch_size=32, shard_index=i,
                              shard_count=2).start()
            for i in range(2)
        ]
        try:
            for w in workers:
                register_worker(disp.target, w.target)
            result = run(TrainArgs(
                model="mnist", steps=8, batch_size=32, log_every=4,
                data_service=f"dispatch://{disp.target}",
            ))
            assert result["final_step"] == 8
            assert np.isfinite(result["loss"])
        finally:
            for w in workers:
                w.stop()
            disp.stop()

    def test_out_of_process_server(self, tmp_path):
        """VERDICT #7 done-criterion: a REAL separate server process (the
        CLI) feeds a training run in this process."""
        wl = get_workload("mnist", batch_size=32)
        path = record_path(str(tmp_path), "mnist")
        stage_synthetic_to_records(wl, path, 256)

        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.Popen(
            [sys.executable, "-m", "distributed_tensorflow_tpu.data.service",
             "--model=mnist", f"--data_dir={tmp_path}", "--batch_size=32"],
            env=env, cwd=REPO, stdout=subprocess.PIPE, text=True,
        )
        try:
            line = proc.stdout.readline()
            assert line.startswith("DATA_SERVICE_READY"), line
            target = line.split()[1]

            from distributed_tensorflow_tpu.train_lib import TrainArgs, run

            result = run(TrainArgs(
                model="mnist", steps=10, batch_size=32, log_every=5,
                data_service=target,
            ))
            assert result["final_step"] == 10
            assert np.isfinite(result["loss"])
        finally:
            proc.terminate()
            proc.wait(timeout=30)


class TestDispatcherFileGroups:
    """Dispatcher tier over a MULTI-FILE dataset (VERDICT r3 #4): each
    worker serves a whole FILE GROUP (tf.data FILE auto-shard), and the
    round-robin client still sees every record exactly once per epoch."""

    @pytest.fixture
    def fileset(self, tmp_path):
        rec = RecordFile([("x", (4,), np.float32), ("label", (), np.int32)])
        rng = np.random.RandomState(0)
        paths = []
        for f in range(4):
            arrays = {
                "x": rng.randn(16, 4).astype(np.float32),
                "label": (np.arange(16) + 100 * f).astype(np.int32),
            }
            p = str(tmp_path / f"idx-{f:05d}-of-00004.rec")
            rec.write(p, arrays)
            paths.append(p)
        return paths, rec

    def test_file_group_workers_cover_one_epoch(self, fileset):
        from distributed_tensorflow_tpu.data.dispatcher import (
            DataServiceDispatcher,
            DistributedDataServiceIterator,
            register_worker,
        )

        paths, rec = fileset
        disp = DataServiceDispatcher().start()
        # 2 workers x 2-file groups: worker i serves files i, i+2.
        workers = [
            DataServiceServer(paths, rec, batch_size=8, shuffle=False,
                              num_threads=1, shard_index=i, shard_count=2,
                              policy="file").start()
            for i in range(2)
        ]
        try:
            for w in workers:
                register_worker(disp.target, w.target)
            it = DistributedDataServiceIterator(disp.target, rec, 8)
            labels = []
            for _ in range(8):  # 64 records / batch 8 = one epoch
                labels.extend(next(it)["label"].tolist())
            want = sorted(i + 100 * f for f in range(4) for i in range(16))
            assert sorted(labels) == want
            it.close()
        finally:
            for w in workers:
                w.stop()
            disp.stop()

    def test_worker_cli_serves_file_group(self, fileset, tmp_path):
        """The worker CLI resolves a fileset from --data_dir and serves its
        file group (out-of-process, 2 processes x 2 files)."""
        import socket as _socket
        import time

        from distributed_tensorflow_tpu.data.records import (
            record_schema,
            stage_synthetic_to_records,
        )
        from distributed_tensorflow_tpu.data.service import (
            DataServiceIterator,
        )
        from distributed_tensorflow_tpu.models import get_workload

        wl = get_workload("mnist", batch_size=16)
        data_dir = tmp_path / "mnist_files"
        stage_synthetic_to_records(
            wl, str(data_dir / "mnist.rec"), 64, chunk=16, num_files=4)
        procs = []
        try:
            for i in range(2):
                procs.append(subprocess.Popen(
                    [sys.executable, "-m",
                     "distributed_tensorflow_tpu.data.service",
                     "--model=mnist", f"--data_dir={data_dir}",
                     "--batch_size=8", f"--shard_index={i}",
                     "--shard_count=2", "--auto_shard_policy=file"],
                    env=dict(os.environ, JAX_PLATFORMS="cpu"),
                    cwd=REPO, stdout=subprocess.PIPE, text=True,
                ))
            targets = []
            for pr in procs:
                line = pr.stdout.readline()
                assert "DATA_SERVICE_READY" in line, line
                targets.append(line.split()[-1].strip())
            schema = record_schema(wl)
            for t in targets:
                it = DataServiceIterator(t, schema, 8)
                batch = next(it)
                assert batch["image"].shape[0] == 8
                it.close()
        finally:
            for pr in procs:
                pr.terminate()
                pr.wait(timeout=10)


class TestDispatcherReadmission:
    """VERDICT r3 weak #8: pins the re-admission semantics — a worker that
    dies and RESTARTS (new port, re-registers) is picked up by NEW streams;
    a running stream never re-admits it mid-epoch (the same contract as
    non-snapshot tf.data service)."""

    def test_restarted_worker_joins_new_streams_not_running_ones(
            self, indexed_record):
        from distributed_tensorflow_tpu.data.dispatcher import (
            DataServiceDispatcher,
            DistributedDataServiceIterator,
            register_worker,
        )

        path, rec, _ = indexed_record
        disp = DataServiceDispatcher().start()
        w0 = DataServiceServer(path, rec, batch_size=8, shuffle=False,
                               num_threads=1, shard_index=0,
                               shard_count=2).start()
        w1 = DataServiceServer(path, rec, batch_size=8, shuffle=False,
                               num_threads=1, shard_index=1,
                               shard_count=2).start()
        restarted = None
        try:
            register_worker(disp.target, w0.target)
            register_worker(disp.target, w1.target)
            it = DistributedDataServiceIterator(disp.target, rec, 8)
            next(it)  # stream is live on both workers
            assert len(it._iters) == 2
            w1.stop()  # worker dies mid-stream
            # drain a few batches: the dead worker is dropped with a
            # warning, the survivor keeps feeding
            for _ in range(4):
                next(it)
            assert len(it._iters) == 1
            # the worker restarts under a NEW port and re-registers
            restarted = DataServiceServer(
                path, rec, batch_size=8, shuffle=False, num_threads=1,
                shard_index=1, shard_count=2).start()
            register_worker(disp.target, restarted.target)
            # the RUNNING stream never re-admits it...
            for _ in range(3):
                next(it)
            assert len(it._iters) == 1
            it.close()
            # ...but a NEW stream connects to the full fleet (the stale
            # dead registration is skipped at connect, the restarted
            # worker serves)
            it2 = DistributedDataServiceIterator(disp.target, rec, 8)
            assert len(it2._iters) == 2
            labels = []
            for _ in range(8):
                labels.extend(next(it2)["label"].tolist())
            assert sorted(labels) == list(range(64))
            it2.close()
        finally:
            for s in (w0, restarted):
                if s is not None:
                    try:
                        s.stop()
                    except Exception:
                        pass
            disp.stop()


class TestDispatcherDurability:
    """VERDICT r4 missing #3: the dispatcher was the one remaining input
    SPOF for NEW participants.  With a registration journal, a SIGKILLed
    and restarted dispatcher serves late-joining consumers; with the
    worker heartbeat, even a journal-less restart re-learns the fleet."""

    def test_sigkilled_dispatcher_restarts_from_journal(
            self, indexed_record, tmp_path):
        from distributed_tensorflow_tpu.data.dispatcher import (
            DistributedDataServiceIterator,
            list_workers,
            register_worker,
        )

        path, rec, _ = indexed_record
        journal = str(tmp_path / "registry.journal")
        port = free_port()
        env = dict(os.environ, JAX_PLATFORMS="cpu")

        def spawn_dispatcher():
            proc = subprocess.Popen(
                [sys.executable, "-m",
                 "distributed_tensorflow_tpu.data.service",
                 "--role=dispatcher", f"--port={port}",
                 f"--journal={journal}"],
                env=env, cwd=REPO, stdout=subprocess.PIPE, text=True,
            )
            line = proc.stdout.readline()
            assert line.startswith("DATA_DISPATCHER_READY"), line
            return proc, line.split()[1]

        disp_proc, target = spawn_dispatcher()
        workers = [
            DataServiceServer(path, rec, batch_size=8, shuffle=False,
                              num_threads=1, shard_index=i,
                              shard_count=2).start()
            for i in range(2)
        ]
        restarted = None
        try:
            for w in workers:
                register_worker(target, w.target)
            it = DistributedDataServiceIterator(target, rec, 8)
            next(it)  # fleet is live

            disp_proc.kill()  # SIGKILL — no shutdown handler runs
            disp_proc.wait(timeout=30)
            # data plane unaffected: the RUNNING stream keeps pulling
            for _ in range(3):
                next(it)
            it.close()

            # restarted dispatcher replays the journal: a LATE-JOINING
            # consumer sees the full fleet although no worker re-registered
            restarted, target2 = spawn_dispatcher()
            assert sorted(list_workers(target2)) == sorted(
                w.target for w in workers)
            late = DistributedDataServiceIterator(target2, rec, 8)
            labels = []
            for _ in range(8):
                labels.extend(next(late)["label"].tolist())
            assert sorted(labels) == list(range(64))
            late.close()
        finally:
            for p in (disp_proc, restarted):
                if p is not None:
                    p.kill()
                    p.wait(timeout=30)
            for w in workers:
                w.stop()

    def test_worker_expires_without_heartbeat(self, tmp_path):
        """expire_after_s: a silent worker drops off the served list while
        a heartbeating one stays, and the journal compacts to the live
        set.  Metadata plane only — no data servers needed."""
        import time

        from distributed_tensorflow_tpu.data.dispatcher import (
            DataServiceDispatcher,
            list_workers,
            register_worker,
        )

        journal = str(tmp_path / "registry.journal")
        disp = DataServiceDispatcher(
            journal_path=journal, expire_after_s=0.6).start()
        try:
            register_worker(disp.target, "10.0.0.1:111")  # will go silent
            register_worker(disp.target, "10.0.0.2:222")  # will heartbeat
            assert sorted(list_workers(disp.target)) == [
                "10.0.0.1:111", "10.0.0.2:222"]
            # Heartbeat .2 past the window's midpoint so only :222 survives.
            for _ in range(4):
                time.sleep(0.2)
                register_worker(disp.target, "10.0.0.2:222")
            assert list_workers(disp.target) == ["10.0.0.2:222"]
            # The journal compacted to the live set (one line, timestamped).
            lines = [l.split() for l in open(journal) if l.strip()]
            assert [l[1] for l in lines] == ["10.0.0.2:222"]
            assert len(lines[0]) == 3
        finally:
            disp.stop()

    def test_stale_journal_entries_dropped_on_replay(self, tmp_path):
        """Replay prunes registrations older than the expiry window;
        legacy two-field lines (no timestamp) replay as fresh."""
        import time

        from distributed_tensorflow_tpu.data.dispatcher import (
            DataServiceDispatcher,
        )

        journal = str(tmp_path / "registry.journal")
        with open(journal, "w") as f:
            f.write(f"R 10.0.0.1:111 {time.time() - 3600:.3f}\n")  # stale
            f.write(f"R 10.0.0.2:222 {time.time():.3f}\n")         # fresh
            f.write("R 10.0.0.3:333\n")                            # legacy
        disp = DataServiceDispatcher(
            journal_path=journal, expire_after_s=60.0)
        assert sorted(disp.workers) == ["10.0.0.2:222", "10.0.0.3:333"]
        # Compacted: the stale line is gone from disk too.
        assert "10.0.0.1:111" not in open(journal).read()
        # Without expiry the same journal replays everything (legacy
        # behavior preserved when the feature is off).
        disp_all = DataServiceDispatcher(journal_path=journal)
        assert len(disp_all.workers) == 2  # the compacted live set
        disp.stop()
        disp_all.stop()

    def test_registration_heartbeat_keeps_worker_alive(self):
        """The existing heartbeat doubles as the liveness signal: a worker
        beating faster than the window survives many windows."""
        import time

        from distributed_tensorflow_tpu.data.dispatcher import (
            DataServiceDispatcher,
            list_workers,
            register_worker,
            start_registration_heartbeat,
        )

        disp = DataServiceDispatcher(expire_after_s=0.5).start()
        beat = None
        try:
            register_worker(disp.target, "10.0.0.9:999")
            beat = start_registration_heartbeat(
                disp.target, "10.0.0.9:999", interval_s=0.1)
            for _ in range(4):  # 4 x 0.3s = several expiry windows
                time.sleep(0.3)
                assert list_workers(disp.target) == ["10.0.0.9:999"]
        finally:
            if beat is not None:
                beat.set()
            disp.stop()

    def test_heartbeat_recovers_journalless_restart(self, indexed_record):
        import time

        from distributed_tensorflow_tpu.data.dispatcher import (
            DataServiceDispatcher,
            DistributedDataServiceIterator,
            list_workers,
            register_worker,
            start_registration_heartbeat,
        )

        path, rec, _ = indexed_record
        port = free_port()
        disp = DataServiceDispatcher(port=port).start()
        worker = DataServiceServer(path, rec, batch_size=8, shuffle=False,
                                   num_threads=1).start()
        beat = None
        disp2 = None
        try:
            register_worker(disp.target, worker.target)
            beat = start_registration_heartbeat(
                disp.target, worker.target, interval_s=0.2)
            disp.stop()  # dispatcher dies, journal-less

            # a new dispatcher on the same address starts EMPTY...
            disp2 = DataServiceDispatcher(port=port).start()
            # ...and re-learns the worker from its heartbeat
            deadline = time.monotonic() + 10
            while (not list_workers(disp2.target)
                   and time.monotonic() < deadline):
                time.sleep(0.1)
            assert list_workers(disp2.target) == [worker.target]
            late = DistributedDataServiceIterator(disp2.target, rec, 8)
            assert next(late)["label"].shape == (8,)
            late.close()
        finally:
            if beat is not None:
                beat.set()
            worker.stop()
            if disp2 is not None:
                disp2.stop()
