"""Host-oracle worker processes for the guard's audit.

``crypto.ed25519.verify`` (OpenSSL through ``cryptography``) keeps the GIL,
so threads cannot run the audit's oracle calls beside the caller's own
packing; processes can.  ``OraclePool`` holds a few persistent children
(``crypto/oracle_worker.py``, started with ``subprocess.Popen`` over pipes:
no ``multiprocessing`` spawn re-importing the parent's ``__main__`` with all
of jax, no fork of a process that holds the chip).  The guard submits the
sampled rows before the device dispatch and collects the verdicts after it.

The pool never decides a lane by itself being unsure: a child that died,
answered short or did not answer in time has its lanes verified by the same
``verify_rows`` on the calling thread, is replaced, and the loss is logged.
"""

from __future__ import annotations

import atexit
import logging
import os
import select
import subprocess
import sys
import threading
import time
from typing import List, Optional, Sequence, Tuple

from tendermint_tpu.crypto import oracle_worker as _wire
from tendermint_tpu.crypto.oracle_worker import Row, verify_rows

logger = logging.getLogger("tendermint_tpu.verify")

# Below this many sampled lanes the oracle runs on the calling thread: a
# frame's pipe round trip reads 0.08-0.13 ms on the v5e's host (my chip run,
# PR 25) and up to a GIL switch interval more under a busy second thread,
# against 0.125 ms a lane inline.  A live node's 1- to 128-lane vote
# dispatches (k <= 7 at the 5 % rate) stay inline.
MIN_POOL_LANES = 8
# A frame carries about 8 ms of oracle before a second worker is asked (0.125
# ms an ed25519 lane through OpenSSL, 4.5 ms a secp256k1 lane in Python): under
# the shortest device dispatch that samples so many (1,280 ed25519 lanes at
# the 5 % rate, about 10 ms).  Each frame is a write and a read that drop the
# GIL; beside a busy thread each costs up to a switch interval (5 ms) to get
# it back, which four frames for a 77-lane sample paid in the sync cell
# (guard.submit 35.8 ms in its small windows; my chip run, PR 25).
_LANES_PER_FRAME = {"ed25519": 64, "secp256k1": 2}
_MAX_WORKERS = 4
_WORKER_MODULE = "tendermint_tpu.crypto.oracle_worker"


def pool_size(cores: Optional[int] = None) -> int:
    """Workers for what this process may run on: two cores stay with the
    caller and the device runtime, at most four verify (5 % of a
    10,240-lane dispatch at 0.125 ms a lane is 16 ms on four, inside the
    dispatch's 33 ms).  Under three cores: none, the audit stays inline."""
    if cores is None:
        cores = len(os.sched_getaffinity(0))
    return max(0, min(_MAX_WORKERS, cores - 2))


class _Lost(Exception):
    """This worker cannot be waited for any longer."""


def _worker_env() -> dict:
    """The parent's environment, with this checkout first on the child's
    import path however the parent came by it."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return dict(os.environ, PYTHONPATH=os.pathsep.join(
        p for p in (root, os.environ.get("PYTHONPATH")) if p))


class _Worker:
    def __init__(self):
        self.proc = subprocess.Popen(
            [sys.executable, "-m", _WORKER_MODULE],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, bufsize=0,
            env=_worker_env(),
        )
        self._w = self.proc.stdin.fileno()
        self._r = self.proc.stdout.fileno()
        os.set_blocking(self._w, False)
        os.set_blocking(self._r, False)
        self.wlock = threading.Lock()
        self.rlock = threading.Lock()
        self.lost = False
        self._rbuf = bytearray()
        self._replies: dict = {}   # id -> verdicts, read ahead of their collector
        self._dropped: set = set()  # ids nobody will collect

    def write(self, frame: bytes, deadline: Optional[float]) -> None:
        view = memoryview(frame)
        with self.wlock:
            if self.lost:
                raise _Lost("worker already lost")
            while view:
                try:
                    view = view[os.write(self._w, view):]
                except BlockingIOError:
                    # the pipe is full: the child is still on an earlier frame
                    if not select.select([], [self._w], [], _left(deadline))[1]:
                        raise _Lost("write timed out")
                except OSError as e:
                    raise _Lost(f"write failed: {e}")

    def _next_reply(self, deadline: Optional[float]) -> Tuple[int, int, List[bool]]:
        buf = self._rbuf
        while True:
            if len(buf) >= 4:
                size = int.from_bytes(buf[:4], "little")
                if size > _wire.MAX_FRAME:
                    raise _Lost("reply stream out of step")
                if len(buf) >= 4 + size:
                    body = bytes(buf[4:4 + size])
                    del buf[:4 + size]
                    return _wire.decode_reply(body)
            try:
                chunk = os.read(self._r, 65536)
            except BlockingIOError:
                if not select.select([self._r], [], [], _left(deadline))[0]:
                    raise _Lost("no answer in time")
                continue
            except OSError as e:
                raise _Lost(f"read failed: {e}")
            if not chunk:
                raise _Lost(f"exited with {self.proc.poll()}")
            buf += chunk

    def reply(self, req_id: int, n: int, deadline: Optional[float]) -> List[bool]:
        """The verdicts of request ``req_id``; replies come in the order the
        requests went, so others' are kept for them on the way."""
        with self.rlock:
            if self.lost:
                raise _Lost("worker already lost")
            while req_id not in self._replies:
                self._keep(*self._next_reply(deadline))
            verdicts = self._replies.pop(req_id)
        if verdicts is None:
            raise _Lost("the worker's oracle raised")
        if len(verdicts) != n:
            raise _Lost(f"answered {len(verdicts)} lanes of {n}")
        return verdicts

    def drop(self, req_id: int) -> None:
        """Nobody will collect ``req_id``: take what has arrived off the pipe
        now, and discard the reply whenever it is read."""
        with self.rlock:
            if self.lost:
                return
            if req_id in self._replies:
                del self._replies[req_id]
                return
            self._dropped.add(req_id)
            try:
                while req_id in self._dropped:
                    self._keep(*self._next_reply(time.monotonic()))
            except _Lost:
                pass  # not there yet (or gone): the next reader finds out

    def _keep(self, rid: int, status: int, verdicts: List[bool]) -> None:
        if rid in self._dropped:
            self._dropped.discard(rid)
        else:
            self._replies[rid] = verdicts if status == 0 else None

    def close(self, kill: bool = False) -> None:
        """End the child: at EOF on its stdin, or now with ``kill``.  The
        pipes are closed under their locks, so no thread still reads or
        writes a descriptor whose number has been given away."""
        self.lost = True
        if kill:
            self.proc.kill()
        if not self.wlock.acquire(timeout=1.0):
            self.proc.kill()  # a writer stood on a full pipe: this ends it
            self.wlock.acquire()
        try:
            self.proc.stdin.close()
        except OSError:
            pass
        finally:
            self.wlock.release()
        try:
            self.proc.wait(timeout=2.0)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()
        with self.rlock:  # the child is gone: a reader has met EOF by now
            self.proc.stdout.close()


def _left(deadline: Optional[float]) -> Optional[float]:
    """Seconds until ``deadline`` (a ``time.monotonic()`` value; None = wait
    as long as the worker lives) in the form ``select`` takes."""
    return None if deadline is None else max(0.0, deadline - time.monotonic())


class Ticket:
    """One submitted sample: its rows, and which worker holds which slice.
    ``collect`` it once; ``abandon`` is a no-op after that."""

    __slots__ = ("pool", "algo", "rows", "parts")

    def __init__(self, pool: "OraclePool", algo: str, rows: Sequence[Row]):
        self.pool = pool
        self.algo = algo
        self.rows = rows
        self.parts: List[Tuple[Optional[_Worker], int, int, int]] = []

    def collect(self, deadline: Optional[float]) -> Tuple[List[bool], int]:
        return self.pool.collect(self, deadline)

    def abandon(self) -> None:
        self.pool.abandon(self)


class OraclePool:
    def __init__(self, size: int):
        if size < 1:
            raise ValueError("an oracle pool needs at least one worker")
        self._mtx = threading.Lock()
        self._next_id = 1
        self._turn = 0
        self._workers = [_Worker() for _ in range(size)]

    def pids(self) -> List[int]:
        with self._mtx:
            return [w.proc.pid for w in self._workers]

    def submit(self, algo: str, rows: Sequence[Row],
               deadline: Optional[float] = None) -> Ticket:
        """Split ``rows`` over as many workers as have _LANES_PER_FRAME
        of ``algo`` each (one, for fewer) and write the frames.  A worker that cannot
        take its frame is marked in the ticket; ``collect`` verifies that
        slice inline."""
        ticket = Ticket(self, algo, rows)
        n = len(rows)
        with self._mtx:
            workers = list(self._workers)
            frames = max(1, min(len(workers), n // _LANES_PER_FRAME[algo]))
            first_id, self._next_id = self._next_id, self._next_id + frames
            # successive small samples start on successive workers
            turn, self._turn = self._turn, self._turn + frames
        for j in range(frames):
            lo, hi = n * j // frames, n * (j + 1) // frames
            w = workers[(turn + j) % len(workers)] if workers else None
            try:
                if w is not None:
                    w.write(_wire.encode_request(first_id + j, algo, rows[lo:hi]),
                            deadline)
            except _Lost as e:
                self._replace(w, str(e))
                w = None
            ticket.parts.append((w, first_id + j, lo, hi))
        return ticket

    def collect(self, ticket: Ticket,
                deadline: Optional[float]) -> Tuple[List[bool], int]:
        """Every row's verdict, in order, and how many of them had to be
        verified here because their worker was lost.  ``deadline`` is a
        ``time.monotonic()`` value; what has arrived by then is still read."""
        verdicts: List[bool] = []
        inline = 0
        parts, ticket.parts = ticket.parts, []
        try:
            while parts:
                w, req_id, lo, hi = parts.pop(0)
                part = None
                if w is not None:
                    try:
                        part = w.reply(req_id, hi - lo, deadline)
                    except _Lost as e:
                        self._replace(w, str(e))
                if part is None:
                    logger.warning(
                        "audit oracle worker lost: %d sampled lanes verified "
                        "on the calling thread", hi - lo)
                    part = verify_rows(ticket.algo, ticket.rows[lo:hi])
                    inline += hi - lo
                verdicts.extend(part)
        finally:
            ticket.parts = parts  # an oracle that raised here: the rest is
            self.abandon(ticket)  # dropped, not left in the pipes
        return verdicts, inline

    def abandon(self, ticket: Ticket) -> None:
        """The dispatch ended on the host: its answers are drained and
        dropped, and the workers stay usable."""
        parts, ticket.parts = ticket.parts, []
        for w, req_id, _lo, _hi in parts:
            if w is not None:
                w.drop(req_id)

    def _replace(self, w: _Worker, why: str) -> None:
        with self._mtx:
            if w.lost:
                return
            w.lost = True
            if w in self._workers:
                at = self._workers.index(w)
                try:
                    self._workers[at] = _Worker()
                except OSError:
                    logger.exception("audit oracle worker could not be replaced")
                    del self._workers[at]
        logger.warning(
            "audit oracle worker pid=%d lost (%s): killed and replaced",
            w.proc.pid, why)
        w.close(kill=True)

    def close(self) -> None:
        with self._mtx:
            workers, self._workers = self._workers, []
        for w in workers:
            w.close()


_pool_lock = threading.Lock()
_pool: Optional[OraclePool] = None
_pool_tried = False


def get_oracle_pool() -> Optional[OraclePool]:
    """The process's pool, started at the first audit large enough to use it;
    None where the process has too few cores for one (or it could not be
    started: said once, and the audit stays inline)."""
    global _pool, _pool_tried
    with _pool_lock:
        if not _pool_tried:
            _pool_tried = True
            size = pool_size()
            if size:
                try:
                    _pool = OraclePool(size)
                except OSError:
                    logger.exception(
                        "audit oracle workers could not be started; the audit "
                        "runs on the calling thread")
        return _pool


def close_oracle_pool() -> None:
    """Stop the children (also at exit); the next large audit starts new ones."""
    global _pool, _pool_tried
    with _pool_lock:
        pool, _pool, _pool_tried = _pool, None, False
    if pool is not None:
        pool.close()


atexit.register(close_oracle_pool)
