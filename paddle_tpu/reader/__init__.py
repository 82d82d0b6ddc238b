"""Reader decorators.

Parity: python/paddle/reader/decorator.py — batch/shuffle/buffered/
map_readers/xmap_readers/chain/compose/firstn, plus the async device
prefetch pipeline (pipeline.py) replacing the reference's double-buffer
/ py_reader C++ queue.
"""
import itertools
import random
import threading
import time
import queue as _queue

from .. import telemetry as _tm

__all__ = ["batch", "shuffle", "buffered", "map_readers", "xmap_readers",
           "chain", "compose", "firstn", "cache", "Pipeline", "creator",
           "ComposeNotAligned", "PipeReader", "multiprocess_reader",
           "Fake"]


class ComposeNotAligned(ValueError):
    """Raised by compose(check_alignment=True) when the input readers
    yield different numbers of samples (ref decorator.py)."""


def batch(reader, batch_size, drop_last=True):
    def batched():
        b = []
        for item in reader():
            b.append(item)
            if len(b) == batch_size:
                yield b
                b = []
        if b and not drop_last:
            yield b
    return batched


def shuffle(reader, buf_size):
    def shuffled():
        rng = random.Random(0)
        buf = []
        for item in reader():
            buf.append(item)
            if len(buf) >= buf_size:
                rng.shuffle(buf)
                yield from buf
                buf = []
        rng.shuffle(buf)
        yield from buf
    return shuffled


def buffered(reader, size):
    """Background-thread prefetch buffer (host side)."""
    class _End:
        pass

    def buffered_reader():
        q = _queue.Queue(maxsize=size)

        def worker():
            try:
                for item in reader():
                    q.put(item)
            finally:
                q.put(_End)

        t = threading.Thread(target=worker, daemon=True)
        t.start()
        while True:
            item = q.get()
            if item is _End:
                break
            yield item
    return buffered_reader


def map_readers(func, *readers):
    def reader():
        its = [r() for r in readers]
        for vals in zip(*its):
            yield func(*vals)
    return reader


def xmap_readers(mapper, reader, process_num=4, buffer_size=16,
                 order=False):
    """Parallel map via threads (ref xmap_readers)."""
    def xreader():
        in_q = _queue.Queue(buffer_size)
        out_q = _queue.Queue(buffer_size)
        END = object()

        def feeder():
            for i, item in enumerate(reader()):
                in_q.put((i, item))
            for _ in range(process_num):
                in_q.put(END)

        def worker():
            while True:
                got = in_q.get()
                if got is END:
                    out_q.put(END)
                    return
                i, item = got
                out_q.put((i, mapper(item)))

        threading.Thread(target=feeder, daemon=True).start()
        workers = [threading.Thread(target=worker, daemon=True)
                   for _ in range(process_num)]
        for w in workers:
            w.start()
        finished = 0
        pending = {}
        next_i = 0
        while finished < process_num:
            got = out_q.get()
            if got is END:
                finished += 1
                continue
            if not order:
                yield got[1]
            else:
                pending[got[0]] = got[1]
                while next_i in pending:
                    yield pending.pop(next_i)
                    next_i += 1
        if order:
            for i in sorted(pending):
                yield pending[i]
    return xreader


def chain(*readers):
    def reader():
        for r in readers:
            yield from r()
    return reader


def compose(*readers, check_alignment=True):
    """Flatten N readers' outputs into one tuple stream. With
    check_alignment (the reference default) a reader running short
    raises ComposeNotAligned; without it, trailing output is dropped."""
    _SHORT = object()

    def reader():
        its = [r() for r in readers]
        zipper = (itertools.zip_longest(*its, fillvalue=_SHORT)
                  if check_alignment else zip(*its))
        for vals in zipper:
            out = []
            for v in vals:
                if v is _SHORT:
                    raise ComposeNotAligned(
                        "outputs of composed readers are not aligned")
                if isinstance(v, tuple):
                    out.extend(v)
                else:
                    out.append(v)
            yield tuple(out)
    return reader


def firstn(reader, n):
    def reader_n():
        yield from itertools.islice(reader(), n)
    return reader_n


def cache(reader):
    data = []

    def cached():
        if not data:
            for item in reader():
                data.append(item)
                yield item
        else:
            yield from data
    return cached


def multiprocess_reader(readers, use_pipe=True, queue_size=1000):
    """Run each reader in its own OS process, merging samples into one
    stream (ref decorator.py:338 — the data-loading analog of the
    reference's multi-process reader; order across readers is arrival
    order). Both modes carry pickled samples: `use_pipe=True` uses one
    multiprocessing.Pipe per reader (no /dev/shm requirement),
    otherwise a shared bounded Queue.

    The children are fork()ed (the readers are closures), so under a
    live TPU runtime they inherit the parent's libtpu state without
    owning the chip: a reader that touches JAX in the child fails or
    hangs. Readers must stay numpy/python-only; not exercised on a
    chip yet (chip_smoke.py feeds in-process)."""
    import multiprocessing

    if not isinstance(readers, list) or not readers:
        raise ValueError("readers must be a non-empty list")

    def _pump_queue(r, q):
        try:
            for sample in r():
                if sample is None:
                    raise ValueError(
                        "multiprocess_reader sample is None")
                q.put(sample)
        finally:
            # ALWAYS enqueue the end sentinel — a child that raised
            # without it would leave the consumer blocked forever
            q.put(None)

    def queue_reader():
        import queue as _q
        q = multiprocessing.Queue(queue_size)
        procs = [multiprocessing.Process(target=_pump_queue,
                                         args=(r, q), daemon=True)
                 for r in readers]
        for p in procs:
            p.start()
        live = len(readers)
        try:
            while live:
                try:
                    sample = q.get(timeout=5.0)
                except _q.Empty:
                    # sentinel can be lost to a SIGKILLed child; detect
                    # dead producers instead of blocking forever
                    if all(not p.is_alive() for p in procs):
                        dead = [p.exitcode for p in procs]
                        if any(code not in (0, None) for code in dead):
                            raise RuntimeError(
                                "multiprocess_reader child died "
                                f"(exit codes {dead})")
                        live = 0
                    continue
                if sample is None:
                    live -= 1
                else:
                    yield sample
        finally:
            for p in procs:
                p.join()
            # a child that raised exits nonzero AFTER its sentinel —
            # surface the failure instead of silently truncating data
            bad = [p.exitcode for p in procs if p.exitcode]
            if bad:
                raise RuntimeError(
                    f"multiprocess_reader child failed (exit {bad})")

    def _pump_pipe(r, conn):
        for sample in r():
            if sample is None:
                raise ValueError("multiprocess_reader sample is None")
            conn.send(sample)
        conn.send(None)
        conn.close()

    def pipe_reader():
        conns, procs, owner = [], [], {}
        broken = []
        for i, r in enumerate(readers):
            parent, child = multiprocessing.Pipe(duplex=False)
            conns.append(parent)
            p = multiprocessing.Process(target=_pump_pipe,
                                        args=(r, child), daemon=True)
            procs.append(p)
            owner[parent] = (i, p)
            p.start()
            child.close()
        try:
            while conns:
                for conn in multiprocessing.connection.wait(conns):
                    try:
                        sample = conn.recv()
                    except EOFError:
                        # child died mid-stream (raised or was killed)
                        # without sending its end sentinel — record it
                        # and keep draining the healthy pipes
                        conn.close()
                        conns.remove(conn)
                        broken.append(owner[conn])
                        continue
                    if sample is None:
                        conn.close()
                        conns.remove(conn)
                    else:
                        yield sample
        finally:
            for p in procs:
                p.join()
            # mirror queue mode: a child that raised exits nonzero (or
            # closed its pipe early) — surface it, never truncate data
            # silently
            failed = [f"reader[{i}] (exit {p.exitcode})"
                      for i, p in broken]
            failed += [f"reader[{i}] (exit {p.exitcode})"
                       for i, p in enumerate(procs)
                       if p.exitcode and (i, p) not in broken]
            if failed:
                raise RuntimeError(
                    "multiprocess_reader child failed: "
                    + ", ".join(failed))

    return pipe_reader if use_pipe else queue_reader


class PipeReader:
    """Stream a shell command's stdout ("cat part.gz", "hadoop fs -cat
    ...") and yield decoded lines (ref decorator.py:438). file_type
    "plain" or "gzip" (gzip decompressed incrementally)."""

    def __init__(self, command, bufsize=8192, file_type="plain"):
        import shlex
        import subprocess
        import zlib
        if not isinstance(command, str):
            raise TypeError("command must be a string")
        if file_type not in ("plain", "gzip"):
            raise TypeError(f"file_type {file_type} is not allowed")
        if file_type == "gzip":
            # wbits offset 32: accept gzip or zlib headers
            self._dec = zlib.decompressobj(32 + zlib.MAX_WBITS)
        self.file_type = file_type
        self.bufsize = bufsize
        self.process = subprocess.Popen(shlex.split(command),
                                        bufsize=bufsize,
                                        stdout=subprocess.PIPE)

    def _gunzip(self, chunk):
        """Incrementally decompress, handling MULTI-MEMBER gzip (e.g.
        `cat part1.gz part2.gz` or pigz output): when one member's
        trailer lands mid-chunk, re-feed the remainder to a fresh
        decompressobj instead of dropping it."""
        import zlib
        out = self._dec.decompress(chunk)
        while self._dec.eof and self._dec.unused_data:
            rest = self._dec.unused_data
            self._dec = zlib.decompressobj(32 + zlib.MAX_WBITS)
            out += self._dec.decompress(rest)
        return out

    def get_line(self, cut_lines=True, line_break="\n"):
        pending = ""
        while True:
            chunk = self.process.stdout.read(self.bufsize)
            if not chunk:
                break
            if self.file_type == "gzip":
                chunk = self._gunzip(chunk)
            text = chunk.decode("utf-8", "replace")
            if not cut_lines:
                yield text
                continue
            pending += text
            *lines, pending = pending.split(line_break)
            yield from lines
        # reap the command FIRST: a failing `cat`/`hadoop fs -cat`
        # must surface as a command error, not be misdiagnosed as a
        # truncated gzip stream (and must never leak unreaped)
        rc = self.process.wait()
        if rc:
            raise IOError(
                f"PipeReader: command exited with status {rc}")
        if self.file_type == "gzip":
            # flush whatever the decompressor still buffers, and detect
            # a truncated stream (missing gzip trailer) instead of
            # silently yielding a short line stream
            tail = self._dec.flush()
            if not self._dec.eof:
                raise IOError(
                    "PipeReader: gzip stream ended before the trailer "
                    "(truncated input)")
            if tail:
                text = tail.decode("utf-8", "replace")
                if not cut_lines:
                    yield text
                else:
                    pending += text
                    *lines, pending = pending.split(line_break)
                    yield from lines
        if cut_lines and pending:
            yield pending


class Fake:
    """Cache the first sample of a real reader and replay it data_num
    times — isolates input cost from compute for speed testing (ref
    decorator.py:509)."""

    def __init__(self):
        self.data = None
        self.yield_num = 0

    def __call__(self, reader, data_num):
        def fake_reader():
            if self.data is None:
                self.data = next(reader())
            while self.yield_num < data_num:
                yield self.data
                self.yield_num += 1
            self.yield_num = 0
        return fake_reader


class Pipeline:
    """Host→device async feed pipeline (double-buffer analog of the
    reference's py_reader/double_buffer; JAX dispatch is async so one
    background thread keeping N feeds in flight overlaps input with
    compute). Uses the C++ ring buffer from native/ when built."""

    def __init__(self, reader, feeder, depth=2):
        self.reader = reader
        self.feeder = feeder
        self.depth = depth

    def __iter__(self):
        import numpy as np
        q = _queue.Queue(maxsize=self.depth)
        END = object()

        def worker():
            try:
                for batch_data in self.reader():
                    fed = self.feeder.feed(batch_data)
                    if _tm.enabled():
                        t0 = time.perf_counter()
                        q.put(fed)
                        _tm.histogram(
                            "pipeline.producer_wait_seconds").observe(
                            time.perf_counter() - t0)
                    else:
                        q.put(fed)
            finally:
                q.put(END)

        threading.Thread(target=worker, daemon=True).start()
        while True:
            if _tm.enabled():
                _tm.gauge("pipeline.queue_depth").set(q.qsize())
                t0 = time.perf_counter()
                item = q.get()
                _tm.histogram(
                    "pipeline.consumer_wait_seconds").observe(
                    time.perf_counter() - t0)
            else:
                item = q.get()
            if item is END:
                return
            if _tm.enabled():
                _tm.counter("pipeline.batches").inc()
            yield item


from . import creator  # noqa: E402  (ref python/paddle/reader/creator.py)
