"""Subprocess line-protocol runners for streaming jobs.

≈ ``org.apache.hadoop.streaming.{PipeMapRed,PipeMapper,PipeReducer}``
(reference: src/contrib/streaming/src/java/org/apache/hadoop/streaming/
PipeMapRed.java:50). Contracts kept:

- records cross the pipe as ``key<TAB>value<NL>`` lines; output lines split
  at the first tab (``stream.map.output.field.separator`` honored);
- the REDUCER child receives the sorted stream and does its own grouping —
  streaming reducers see lines, not grouped keys (classic Hadoop streaming
  semantics);
- the stderr side-channel: ``reporter:counter:<group>,<name>,<amount>`` and
  ``reporter:status:<msg>`` update real counters/status
  (≈ PipeMapRed.MRErrorThread);
- job conf is exported to the child environment with dots → underscores
  (≈ PipeMapRed.addJobConfToEnvironment).
"""

from __future__ import annotations

import os
import shlex
import subprocess
import threading
from typing import Any, BinaryIO

from tpumr.mapred.api import MapRunnable, OutputCollector, Reducer, Reporter
from tpumr.streaming.typedbytes import read_pairs, write_pair


def _child_env(conf: Any) -> dict:
    env = dict(os.environ)
    for k, v in conf:
        if isinstance(v, (str, int, float, bool)):
            env[str(k).replace(".", "_")] = str(v)
    return env


def _split_line(line: bytes, sep: bytes) -> tuple[str, str]:
    head, tab, tail = line.partition(sep)
    return head.decode("utf-8", "replace"), tail.decode("utf-8", "replace")


def _stderr_pump(stream: BinaryIO, reporter: Reporter) -> threading.Thread:
    """Parse the reporter: protocol off the child's stderr
    (≈ PipeMapRed.MRErrorThread); everything else is passed through."""

    def run() -> None:
        for raw in stream:
            line = raw.decode("utf-8", "replace").rstrip("\n")
            if line.startswith("reporter:counter:"):
                try:
                    group, name, amount = line[len("reporter:counter:"):] \
                        .split(",", 2)
                    reporter.incr_counter(group, name, int(amount))
                    continue
                except ValueError:
                    pass
            elif line.startswith("reporter:status:"):
                reporter.set_status(line[len("reporter:status:"):])
                continue
            import sys
            print(line, file=sys.stderr)

    t = threading.Thread(target=run, name="stream-stderr", daemon=True)
    t.start()
    return t


class _StreamProcess:
    """One child + stdin writer / stdout reader plumbing shared by the map
    and reduce sides. ``in_mode``/``out_mode`` select the wire format each
    direction: "text" (key<TAB>value lines) or "typedbytes" (binary-safe
    typed frames ≈ -io typedbytes, typedbytes/TypedBytesInput.java)."""

    def __init__(self, conf: Any, command: str, output: OutputCollector,
                 reporter: Reporter, in_mode: str = "text",
                 out_mode: str = "text") -> None:
        self.sep = conf.get("stream.map.output.field.separator", "\t") \
            .encode("utf-8")
        self.in_mode = in_mode
        self.out_mode = out_mode
        self.proc = subprocess.Popen(
            shlex.split(command), env=_child_env(conf),
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE)
        self._err_thread = _stderr_pump(self.proc.stderr, reporter)
        self._out_error: BaseException | None = None
        self._out_thread = threading.Thread(
            target=self._drain_stdout, args=(output,),
            name="stream-stdout", daemon=True)
        self._out_thread.start()

    def _drain_stdout(self, output: OutputCollector) -> None:
        try:
            if self.out_mode == "typedbytes":
                for k, v in read_pairs(self.proc.stdout):
                    output.collect(k, v)
                return
            for raw in self.proc.stdout:
                line = raw.rstrip(b"\n")
                if not line:
                    continue
                k, v = _split_line(line, self.sep)
                output.collect(k, v)
        except BaseException as e:  # noqa: BLE001 — surfaced by finish()
            self._out_error = e
            # keep draining so a still-writing child never blocks on a
            # full pipe (which would hang finish()'s proc.wait forever)
            try:
                while self.proc.stdout.read(65536):
                    pass
            except OSError:
                pass

    def write_record(self, key: Any, value: Any) -> None:
        if self.in_mode == "typedbytes":
            write_pair(self.proc.stdin, key, value)
            return
        self.proc.stdin.write(f"{key}\t{value}\n".encode("utf-8"))

    def write_line(self, value: Any) -> None:
        self.proc.stdin.write(f"{value}\n".encode("utf-8"))

    def finish(self, what: str) -> None:
        try:
            self.proc.stdin.close()
        except BrokenPipeError:
            pass    # the child is gone already: its rc below says why
        self._out_thread.join()
        self._err_thread.join()
        rc = self.proc.wait()
        if rc != 0:
            raise RuntimeError(
                f"streaming {what} exited rc={rc} "
                f"(≈ PipeMapRed 'subprocess failed with code')")
        if self._out_error is not None:
            raise RuntimeError(
                f"streaming {what} output protocol error: "
                f"{self._out_error}") from self._out_error


class StreamMapRunner(MapRunnable):
    """Map side ≈ PipeMapper: stream every input record to the child, collect
    its stdout lines."""

    def __init__(self) -> None:
        self.conf: Any = None

    def configure(self, conf: Any) -> None:
        self.conf = conf

    def run(self, reader, output, reporter, task_ctx=None) -> None:
        command = self.conf.get("stream.map.command")
        if not command:
            raise ValueError("streaming job missing stream.map.command")
        in_mode = self.conf.get("stream.map.input", "text")
        out_mode = self.conf.get("stream.map.output", "text")
        # text input feeds the child only the line, not the byte offset
        # (≈ PipeMapper.ignoreKey for TextInputFormat); typed-bytes input
        # always frames full pairs (≈ PipeMapper with -io typedbytes)
        ignore_key = in_mode != "typedbytes" and self.conf.get_boolean(
            "stream.map.input.ignoreKey",
            self.conf.get_input_format().__name__ == "TextInputFormat")
        child = _StreamProcess(self.conf, command, output, reporter,
                               in_mode=in_mode, out_mode=out_mode)
        try:
            for key, value in reader:
                if ignore_key:
                    child.write_line(value)
                else:
                    child.write_record(key, value)
        finally:
            child.finish("mapper")


class StreamReducer(Reducer):
    """Reduce side ≈ PipeReducer: the child consumes the whole sorted
    partition as lines and groups keys itself."""

    def __init__(self) -> None:
        self.conf: Any = None
        self._child: _StreamProcess | None = None

    def configure(self, conf: Any) -> None:
        self.conf = conf

    def reduce(self, key, values, output, reporter) -> None:
        if self._child is None:
            command = self.conf.get("stream.reduce.command")
            if not command:
                raise ValueError("streaming job missing stream.reduce.command")
            self._child = _StreamProcess(
                self.conf, command, output, reporter,
                in_mode=self.conf.get("stream.reduce.input", "text"),
                out_mode=self.conf.get("stream.reduce.output", "text"))
        for v in values:
            self._child.write_record(key, v)

    def close(self) -> None:
        if self._child is not None:
            try:
                self._child.finish("reducer")
            finally:
                self._child = None


class StreamCombiner(StreamReducer):
    """Combiner through a child process (``stream.combine.command``) — one
    child per spill, since a combiner must see a complete sorted buffer."""

    def reduce(self, key, values, output, reporter) -> None:
        if self._child is None:
            command = self.conf.get("stream.combine.command")
            if not command:
                raise ValueError("streaming job missing stream.combine.command")
            self._child = _StreamProcess(
                self.conf, command, output, reporter,
                in_mode=self.conf.get("stream.reduce.input", "text"),
                out_mode=self.conf.get("stream.reduce.output", "text"))
        for v in values:
            self._child.write_record(key, v)
