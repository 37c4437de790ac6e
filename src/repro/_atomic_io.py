"""Atomic filesystem primitives shared by the checkpointers.

One home for the crash-safety discipline both the train checkpointer
(`train/checkpoint.py`) and the sketch-job checkpointer
(`stream/resilience.py`) rely on, so the atomicity logic cannot drift
between them:

  * **tmp-then-replace** — every durable artifact (a checkpoint directory,
    a manifest, a heartbeat file) is fully written to a sibling temp path
    and then moved into place with ``os.replace``, which is atomic on
    POSIX: a reader never observes a half-written checkpoint, and a crash
    mid-save never corrupts the previous one.
  * **async writer** — a single daemon thread drains a queue of write
    thunks so the hot loop overlaps checkpoint IO with compute; failures
    are sticky and re-raised on ``wait()`` instead of dying silently on
    the worker thread.
"""

from __future__ import annotations

import json
import os
import queue
import shutil
import threading
from pathlib import Path
from typing import Callable, Optional

__all__ = ["atomic_write_dir", "atomic_write_json", "AsyncWriter"]


def atomic_write_json(path: str | Path, doc: dict, *, indent: int = 1,
                      sort_keys: bool = False) -> Path:
    """Atomically write ``doc`` as JSON: temp file in the same directory,
    then ``os.replace`` — readers see the old content or the new, never a
    torn write."""
    path = Path(path)
    tmp = path.with_name(path.name + ".tmp")
    tmp.write_text(json.dumps(doc, indent=indent, sort_keys=sort_keys))
    os.replace(tmp, path)
    return path


def atomic_write_dir(final: str | Path, writer: Callable[[Path], None], *,
                     manifest: Optional[dict] = None,
                     manifest_name: str = "manifest.json") -> Path:
    """Atomically materialize a directory: ``writer(tmp)`` populates
    ``<final>.tmp``, an optional ``manifest`` dict is serialized last
    (so a manifest's presence certifies a complete payload), then the tmp
    dir is ``os.replace``d over ``final``.  A crash at any point leaves
    either the previous ``final`` intact or a stale ``.tmp`` that the next
    save clears."""
    final = Path(final)
    tmp = final.with_name(final.name + ".tmp")
    if tmp.exists():
        shutil.rmtree(tmp)
    tmp.mkdir(parents=True)
    writer(tmp)
    if manifest is not None:
        (tmp / manifest_name).write_text(json.dumps(manifest))
    if final.exists():
        shutil.rmtree(final)
    os.replace(tmp, final)
    return final


class AsyncWriter:
    """Single-threaded async executor for checkpoint writes.

    ``submit`` enqueues a zero-arg thunk and returns immediately; the
    daemon worker runs thunks in order.  The first failure is stored and
    re-raised (wrapped) on the next ``wait()``/``close()`` — the standard
    contract for checkpoint writers: the train loop learns about a bad
    disk at the next barrier, not by losing the thread."""

    def __init__(self, name: str = "repro-atomic-io"):
        self._q: queue.Queue = queue.Queue()
        self._err: Optional[BaseException] = None
        self._thread = threading.Thread(target=self._worker, daemon=True,
                                        name=name)
        self._thread.start()

    def submit(self, fn: Callable[[], None]) -> None:
        self._q.put(fn)

    def drain(self) -> None:
        """Block until the queue drains; a failed write stays stored."""
        self._q.join()

    def wait(self) -> None:
        """Block until the queue drains; raise if any write failed."""
        self.drain()
        if self._err:
            raise RuntimeError("async checkpoint writer failed") from self._err

    def close(self) -> None:
        self.wait()

    def _worker(self) -> None:
        while True:
            fn = self._q.get()
            try:
                fn()
            except BaseException as e:  # surfaced on next wait()
                self._err = e
            finally:
                self._q.task_done()
