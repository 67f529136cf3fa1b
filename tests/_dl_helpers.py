"""Picklable dataset helpers for the multiprocess DataLoader tests (spawn
workers re-import this module, so the classes must live at module scope)."""
import os

import numpy as np

from paddle_tpu.io import Dataset


class RangeSquareDataset(Dataset):
    def __init__(self, n):
        self.n = n

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        return np.asarray([i, i * i], np.float32)


class CrashingDataset(Dataset):
    """Hard-kills the worker process on a poisoned index (simulates a
    segfaulting C extension, not a catchable Python error)."""

    def __init__(self, n, poison):
        self.n = n
        self.poison = poison

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        if i == self.poison:
            os._exit(13)
        return np.asarray([i], np.float32)


class RaisingDataset(Dataset):
    def __init__(self, n, bad):
        self.n = n
        self.bad = bad

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        if i == self.bad:
            raise ValueError(f"bad sample {i}")
        return np.asarray([i], np.float32)


class WorkerIdDataset(Dataset):
    """Returns the worker id serving each index (get_worker_info check)."""

    def __len__(self):
        return 16

    def __getitem__(self, i):
        from paddle_tpu.io.dataloader import get_worker_info
        info = get_worker_info()
        return np.asarray([i, -1 if info is None else info.id], np.float32)


def _ring_producer(name):
    os.environ["JAX_PLATFORMS"] = "cpu"
    from paddle_tpu.csrc import ShmRing
    w = ShmRing.open(name)
    for i in range(10):
        w.push(bytes([i]) * 1000)
    w.close(unlink=False)
