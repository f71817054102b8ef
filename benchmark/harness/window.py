"""The measured window, picked from the campaign's own event stream, and
the arithmetic on the two heartbeat records that bound it.

A `heartbeat` record carries `ts` and the whole registry dump.  Every
number the harness reports from the program is the difference of one
registry key between the window's first and last heartbeat, so nothing
here depends on when the harness looked.
"""

from __future__ import annotations

import json
import time
from typing import Iterator, Optional


class WindowError(Exception):
    """The stream cannot give a sound window: the run ends non-zero."""


class WindowPicker:
    """Feed it records in order.

    The window starts at the first heartbeat whose `backend.batches` has
    reached `warm_batches`, and ends at the first heartbeat at least
    `seconds` later.  A `compile` event inside the window restarts it at
    the next heartbeat, so nothing compiles inside the window that is
    measured (a campaign meets a new program, the next chunk rung or the
    batch-loop fallback of a window, whenever its testcases first need
    it).  More than MAX_RESTARTS is an error: something keeps compiling,
    and no rate taken around it means anything.
    """

    MAX_RESTARTS = 3

    def __init__(self, seconds: float, warm_batches: int):
        self.seconds = float(seconds)
        self.warm_batches = int(warm_batches)
        self.first: Optional[dict] = None
        self.last: Optional[dict] = None
        self.restarts = 0
        self.heartbeats = 0          # inside the window, first included

    @property
    def done(self) -> bool:
        return self.last is not None

    def feed(self, record: dict) -> None:
        if self.done:
            return
        kind = record.get("type")
        if kind == "compile" and self.first is not None:
            if self.restarts >= self.MAX_RESTARTS:
                raise WindowError(
                    f"compile number {self.restarts + 1} inside the "
                    f"measured window: {_brief(record)}")
            self.restarts += 1
            self.first = None
            self.heartbeats = 0
        elif kind == "heartbeat":
            batches = record.get("metrics", {}).get("backend.batches", 0)
            if self.first is None:
                if batches >= self.warm_batches:
                    self.first = record
                    self.heartbeats = 1
                return
            self.heartbeats += 1
            if record["ts"] - self.first["ts"] >= self.seconds:
                self.last = record


def _brief(record: dict) -> str:
    return json.dumps({k: v for k, v in record.items()
                       if k not in ("metrics", "argv")}, default=str)


def follow(path, alive, poll: float = 0.2) -> Iterator[Optional[dict]]:
    """Records of a JSONL file as they are appended.  Yields None after
    each poll that found nothing, so that the caller can look at its
    clocks.  Ends once `alive()` is false and the file is read to its end.
    A torn last line is left for the next poll."""
    buf = ""
    fh = None
    try:
        while True:
            if fh is None:
                try:
                    fh = open(path, "r", encoding="utf-8")
                except FileNotFoundError:
                    fh = None
            got = False
            if fh is not None:
                chunk = fh.read()
                if chunk:
                    buf += chunk
                    *lines, buf = buf.split("\n")
                    for line in lines:
                        if not line.strip():
                            continue
                        try:
                            yield json.loads(line)
                            got = True
                        except ValueError:
                            continue
            if not got:
                if not alive():
                    return
                yield None
                time.sleep(poll)
    finally:
        if fh is not None:
            fh.close()


def read_key(record: dict, key: str) -> Optional[float]:
    """One registry key of a heartbeat.  `window_s` is the record's clock;
    `phase.seconds.<span path>` is an entry of the span table inside the
    dump.  None where the record has no such key."""
    if key == "window_s":
        return float(record["ts"])
    metrics = record.get("metrics", {})
    if key.startswith("phase.seconds."):
        value = metrics.get("phase.seconds", {}).get(
            key[len("phase.seconds."):])
    else:
        value = metrics.get(key)
    if isinstance(value, dict):          # a labelled counter: its total
        value = sum(value.values())
    return None if value is None else float(value)


def delta(first: dict, last: dict, key: str) -> Optional[float]:
    """last - first of one key; a key only the last record has started
    from 0; None where neither record has it."""
    a, b = read_key(first, key), read_key(last, key)
    if a is None and b is None:
        return None
    return (b or 0.0) - (a or 0.0)


def evaluate(source: dict, first: dict, last: dict) -> Optional[float]:
    """A `heartbeat_delta` metric: scale * sum(numerator deltas) /
    sum(denominator deltas).  A key absent from both heartbeats reads 0 in
    the numerator and makes the metric absent in the denominator, as does
    a denominator of 0: the reader found nothing to read."""
    num = sum(delta(first, last, k) or 0.0 for k in source["numerator"])
    den = 0.0
    for key in source["denominator"]:
        d = delta(first, last, key)
        if d is None:
            return None
        den += d
    if den == 0:
        return None
    return float(source.get("scale", 1.0)) * num / den
