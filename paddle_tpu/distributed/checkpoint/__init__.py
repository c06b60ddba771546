"""Distributed (sharded) checkpoint with reshard-on-load.

API parity with `python/paddle/distributed/checkpoint/`:
``save_state_dict`` / ``load_state_dict``. Format is mesh-independent
(global offsets + shapes), so parallelism configs can change between save
and load — the hard requirement for elastic resume and the 7B→70B config
ladder (SURVEY §5.4).

Crash safety: saves are atomic (staging dir → rename → ``COMMITTED``
marker last, per-shard CRC32 in the metadata — ``commit.py``), storage
I/O retries with backoff (``storage.py``), async-save failures re-raise
on the main thread instead of dying with the daemon writer, and
``faults.py`` is a seeded injector that makes all of it testable:

- :class:`Snapshotter` / :func:`resume` — in-memory peer-replicated
  snapshots (``snapshot.py`` + ``replicator.py``): host-RAM capture every
  ``PADDLE_TPU_SNAP_EVERY`` steps with ring-neighbor replication, and the
  recovery ladder own-RAM → depot copy → peer replica → committed disk
  (``resume_source=memory|peer|disk``, RPO = steps not intervals);
- :func:`latest_checkpoint` — newest *committed* checkpoint under a root
  (interrupted saves are invisible to resume);
- :func:`gc_checkpoints` — keep-N retention sweep;
- :func:`is_committed` — commit-marker check for one directory;
- :class:`CheckpointError` / :class:`CheckpointCorruptionError` /
  :class:`AsyncSaveError` — the failure classes loads/saves raise.
"""

from . import faults  # noqa: F401  (fault-injection API: faults.inject(...))
from . import replicator  # noqa: F401  (snapshot replication transports)
from .commit import (gc_checkpoints, is_committed,  # noqa: F401
                     latest_checkpoint)
from .errors import (AsyncSaveError, CheckpointCorruptionError,  # noqa: F401
                     CheckpointError)
from .load_state_dict import load_state_dict
from .metadata import LocalTensorIndex, LocalTensorMetadata, Metadata
from .save_state_dict import save_state_dict
from .snapshot import (ResumeInfo, Snapshotter,  # noqa: F401
                       SnapshotRestoreError, resume)

__all__ = ["save_state_dict", "load_state_dict", "Metadata",
           "LocalTensorMetadata", "LocalTensorIndex",
           "latest_checkpoint", "gc_checkpoints", "is_committed",
           "CheckpointError", "CheckpointCorruptionError", "AsyncSaveError",
           "faults", "replicator",
           "Snapshotter", "SnapshotRestoreError", "ResumeInfo", "resume"]
