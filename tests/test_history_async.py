"""The async job-history writer (``tpumr.history.async``): ordering,
read-your-writes (every reader flushes first), bounded-queue drop
accounting, and synchronous fallback after stop()."""

import os
import threading

from tpumr.mapred.history import JobHistory
from tpumr.mapred.jobconf import JobConf


class TestAsyncHistory:
    def _history(self, tmp_path, **over):
        conf = JobConf()
        conf.set("tpumr.history.dir", str(tmp_path))
        for k, v in over.items():
            conf.set(k, v)
        return JobHistory(conf)

    def test_readers_see_queued_writes(self, tmp_path):
        h = self._history(tmp_path)
        h.task_event("job_a_0001", "TASK_STARTED",
                     attempt_id="attempt_a_0001_m_000000_0")
        # read-your-writes: every reader flushes the queue first
        state = h.recovered_attempt_state("job_a_0001")
        assert state == {"maps": {}, "reduces": {}}
        assert h.queue_depth() == 0
        assert h.writes_dropped == 0
        h.stop()

    def test_per_file_order_is_enqueue_order(self, tmp_path):
        h = self._history(tmp_path)
        for i in range(50):
            h.task_event("job_b_0001", "E", seq=i)
        assert h.flush()
        events = h.read(os.path.join(str(tmp_path), "job_b_0001.jsonl"))
        assert [e["seq"] for e in events] == list(range(50))
        h.stop()

    def test_bounded_queue_drops_and_counts(self, tmp_path):
        h = self._history(tmp_path, **{"tpumr.history.queue.max": 8})
        gate = threading.Event()
        entered = threading.Event()
        real = h._write_now

        def slow(batch):
            entered.set()
            gate.wait(10.0)
            real(batch)

        h._write_now = slow
        h.task_event("job_c_0001", "E", seq=-1)   # writer picks this up
        assert entered.wait(5.0)
        for i in range(8 + 5):                   # fills queue, 5 dropped
            h.task_event("job_c_0001", "E", seq=i)
        assert h.writes_dropped == 5
        gate.set()
        assert h.flush()
        h.stop()
        events = h.read(os.path.join(str(tmp_path), "job_c_0001.jsonl"))
        assert len(events) == 1 + 8

    def test_post_stop_writes_fall_through_synchronously(self, tmp_path):
        h = self._history(tmp_path)
        h.stop()
        h.task_event("job_d_0001", "LATE")
        events = h.read(os.path.join(str(tmp_path), "job_d_0001.jsonl"))
        assert [e["event"] for e in events] == ["LATE"]

    def test_sync_mode_still_works(self, tmp_path):
        h = self._history(tmp_path, **{"tpumr.history.async": False})
        h.task_event("job_e_0001", "E")
        assert h.queue_depth() == 0
        events = h.read(os.path.join(str(tmp_path), "job_e_0001.jsonl"))
        assert len(events) == 1
        h.stop()
