"""Process-based DataLoader workers (round-2 verdict #8).

Parity target: reference `io/dataloader/dataloader_iter.py:358`
(_DataLoaderIterMultiProcess) — worker processes + shared-memory ndarray
transport, get_worker_info in workers, error propagation with worker
tracebacks, threaded fallback."""

import os
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.io import DataLoader, Dataset, get_worker_info


class Arange(Dataset):
    def __init__(self, n=32, width=8):
        self.n, self.width = n, width

    def __getitem__(self, i):
        x = np.full((self.width,), i, np.float32)
        return x, np.int64(i % 4)

    def __len__(self):
        return self.n


class PidProbe(Dataset):
    def __getitem__(self, i):
        info = get_worker_info()
        return np.asarray([os.getpid(), -1 if info is None else info.id],
                          np.int64)

    def __len__(self):
        return 16


class BigItems(Dataset):
    """Each item is > _SHM_MIN_BYTES so batches ride shared memory."""

    def __getitem__(self, i):
        return np.full((64, 1024), i, np.float32)  # 256 KB

    def __len__(self):
        return 8


class Exploding(Dataset):
    def __getitem__(self, i):
        if i == 5:
            raise ValueError("boom at 5")
        return np.zeros(4, np.float32)

    def __len__(self):
        return 8


class SlowPython(Dataset):
    """A GIL-bound pure-python transform."""

    def __getitem__(self, i):
        acc = 0
        for k in range(150000):
            acc = (acc + k * i) % 97
        return np.asarray([acc], np.float32)

    def __len__(self):
        return 32


class TestProcessWorkers:
    def test_matches_sync_loader(self):
        ds = Arange()
        sync = [tuple(np.asarray(t.numpy()) for t in b)
                for b in DataLoader(ds, batch_size=4, num_workers=0)]
        proc = [tuple(np.asarray(t.numpy()) for t in b)
                for b in DataLoader(ds, batch_size=4, num_workers=2)]
        assert len(sync) == len(proc) == 8
        for (sx, sy), (px, py) in zip(sync, proc):
            np.testing.assert_array_equal(sx, px)
            np.testing.assert_array_equal(sy, py)

    def test_runs_in_separate_processes_with_worker_info(self):
        out = np.concatenate([b.numpy() for b in DataLoader(
            PidProbe(), batch_size=4, num_workers=2)])
        pids = set(out[:, 0].astype(int).tolist())
        ids = set(out[:, 1].astype(int).tolist())
        assert os.getpid() not in pids          # really other processes
        assert len(pids) == 2 and ids == {0, 1}  # both workers served

    def test_shared_memory_roundtrip(self):
        before = set(os.listdir("/dev/shm"))
        batches = list(DataLoader(BigItems(), batch_size=2, num_workers=2,
                                  use_shared_memory=True))
        assert set(os.listdir("/dev/shm")) == before    # none left behind
        assert len(batches) == 4
        for j, b in enumerate(batches):
            arr = b.numpy()
            assert arr.shape == (2, 64, 1024)
            np.testing.assert_array_equal(arr[0], np.full((64, 1024), 2 * j,
                                                          np.float32))

    def test_shared_memory_survives_a_late_reader(self):
        """A queued segment belongs to the parent.  In a process whose
        resource tracker is not yet running when the workers fork (a fresh
        interpreter: hence the subprocess), each worker starts a tracker of
        its own, and that tracker used to unlink the worker's segments when
        the worker exited — before a parent that reads late (a loaded
        machine; here a delay a batch) had attached to them."""
        import subprocess
        import sys
        import textwrap

        script = textwrap.dedent("""
            import time
            import numpy as np
            from paddle_tpu.io import DataLoader, Dataset

            class BigItems(Dataset):
                def __getitem__(self, i):
                    return np.full((64, 1024), i, np.float32)

                def __len__(self):
                    return 8

            n = 0
            for b in DataLoader(BigItems(), batch_size=2, num_workers=2,
                                use_shared_memory=True):
                time.sleep(0.4)
                assert float(b.numpy()[0, 0, 0]) == 2 * n
                n += 1
            print("batches", n)
        """)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        out = subprocess.run(
            [sys.executable, "-c", script], cwd=repo, timeout=120,
            capture_output=True, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert out.returncode == 0 and "batches 4" in out.stdout, out.stderr
        assert "leaked shared_memory" not in out.stderr

    def test_worker_error_propagates_with_traceback(self):
        with pytest.raises(RuntimeError, match="ValueError") as ei:
            list(DataLoader(Exploding(), batch_size=2, num_workers=2))
        assert "boom at 5" in str(ei.value)

    def test_custom_collate_runs_in_worker_and_keeps_types(self):
        def collate(batch):
            return np.stack(batch) * 2.0

        out = list(DataLoader(Arange(8, 4), batch_size=4, num_workers=2,
                              collate_fn=lambda b: collate([x for x, _ in b])))
        assert len(out) == 2
        # a custom collate returning ndarray must yield ndarray in EVERY
        # worker mode (same type as the num_workers=0 path)
        assert isinstance(out[0], np.ndarray)
        np.testing.assert_array_equal(out[0][1], np.full(4, 2.0, np.float32))

    def test_tensor_items_fall_back_to_threads(self):
        import paddle_tpu as paddle

        class TensorDS(Dataset):
            def __getitem__(self, i):
                return paddle.to_tensor(np.full(4, i, np.float32))

            def __len__(self):
                return 8

        # jax arrays are unsafe in forked children: loader must degrade to
        # threads and still produce correct batches
        out = list(DataLoader(TensorDS(), batch_size=4, num_workers=2))
        assert len(out) == 2
        np.testing.assert_array_equal(out[0].numpy()[1], np.full(4, 1.0))

    def test_worker_init_fn_called(self):
        calls = []

        def init(worker_id):
            # fork mode: mutations stay in the worker; use a file instead
            with open(f"/tmp/_dl_init_{os.getppid()}_{worker_id}", "w") as f:
                f.write(str(worker_id))

        list(DataLoader(Arange(8, 4), batch_size=4, num_workers=2,
                        worker_init_fn=init))
        for w in range(2):
            path = f"/tmp/_dl_init_{os.getpid()}_{w}"
            assert os.path.exists(path)
            os.remove(path)

    def test_threaded_fallback_flag(self):
        """use_process_workers=False keeps the threaded pool."""
        ds = PidProbe()
        out = np.concatenate([b.numpy() for b in DataLoader(
            ds, batch_size=4, num_workers=2, use_process_workers=False)])
        assert set(out[:, 0].astype(int).tolist()) == {os.getpid()}

    @pytest.mark.skipif((os.cpu_count() or 1) < 2,
                        reason="GIL-beating speedup needs >1 core")
    def test_python_transform_leaves_the_parent(self):
        """GIL-bound work only scales with processes: with process workers
        the transform's CPU time is spent in the children, not under the
        parent's GIL.  Read as CPU seconds of THIS process, not as a race
        on the wall clock: which pool finishes first depends on how many
        cores the machine has free."""
        ds = SlowPython()
        c0 = time.process_time()
        list(DataLoader(ds, batch_size=4, num_workers=2,
                        use_process_workers=False))
        threaded = time.process_time() - c0
        c0 = time.process_time()
        list(DataLoader(ds, batch_size=4, num_workers=2))
        process = time.process_time() - c0
        assert process < threaded * 0.5
