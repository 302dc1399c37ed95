"""Host-side pipeline: overlap parse/encode/H2D of batch N+1 with the
device work of batch N.

SURVEY §7 names host<->device overlap a hard part ("double-buffer H2D
transfers against device compute or the 5x target dies").  The cold
path profile shows the reference-shaped serial loop — parse -> group-id
encode -> wire encode -> H2D dispatch -> kernel dispatch — spends its
wall clock almost entirely in the three host stages while the device
sits idle (kernel dispatch is async under JAX).  `staged_prefetch`
moves the host stages onto a producer thread with a bounded queue, so
the consumer (kernel dispatch, which must stay ordered — aggregate
state threads through each call) only ever waits when the producer is
genuinely behind.

This pipelining is gated to accelerator execution: the CPU baseline
path stays single-threaded on purpose (BASELINE.md's protocol measures
the engine's own single-thread CPU path as 1.0x, and a threaded
baseline would be measuring a different engine).

Pyarrow parsing and numpy encoding release the GIL for their bulk
work, so a single producer thread buys near-full overlap without
processes or copies.
"""

from __future__ import annotations

import os
import queue
import threading
from typing import Callable, Iterator, Optional

from datafusion_tpu.utils.metrics import METRICS

_DEPTH = 2  # batches in flight: N computing, N+1 staged, N+2 parsing


def pipeline_enabled(device) -> bool:
    """True when batches execute on an accelerator (staging pays for a
    thread only when a device pipeline exists to overlap with).

    `device` is a jax Device or None (= JAX default backend).  The env
    knob DATAFUSION_TPU_PREFETCH forces it on (1) or off (0) — tests
    use 1 to exercise the staged path on CPU meshes.
    """
    knob = os.environ.get("DATAFUSION_TPU_PREFETCH", "auto")
    if knob == "0":
        return False
    if knob == "1":
        return True
    if device is not None:
        return getattr(device, "platform", "cpu") != "cpu"
    import jax

    return jax.default_backend() != "cpu"


class _Stop(Exception):
    pass


def staged_prefetch(
    batches: Iterator,
    stage: Optional[Callable] = None,
    depth: int = _DEPTH,
    wait_timer: str = "pipeline.wait",
) -> Iterator:
    """Yield `batches` in order, pulling and staging them on a
    background thread.

    `stage(batch)` runs on the producer thread right after the batch is
    produced — callers put their host prep there (group-id encode, wire
    encode, H2D dispatch); its results must land in caches the consumer
    re-reads (batch.cache and relation-level caches).  The producer is a
    single thread, so stage() may mutate relation state (encoders,
    dictionaries) without locks — the queue provides the happens-before
    edge to the consumer.

    Exceptions from the source iterator or stage() re-raise in the
    consumer.  Abandoning the generator (early close) stops the
    producer promptly.

    Two stage timers say which side sets the pace: `wait_timer` is the
    consumer blocked on the queue, `pipeline.stage` the producer inside
    stage().
    """
    q: queue.Queue = queue.Queue(maxsize=max(depth, 1))
    stop = threading.Event()
    DONE = object()

    def put(item) -> None:
        while True:
            try:
                q.put(item, timeout=0.1)
                return
            except queue.Full:
                if stop.is_set():
                    raise _Stop() from None

    def producer() -> None:
        try:
            for b in batches:
                if stop.is_set():
                    return
                if stage is not None:
                    with METRICS.timer("pipeline.stage"):
                        stage(b)
                put(b)
            put(DONE)
        except _Stop:
            pass
        except BaseException as e:  # noqa: BLE001 — forwarded to consumer
            try:
                put(e)
            except _Stop:
                pass

    t = threading.Thread(target=producer, name="df-tpu-prefetch", daemon=True)
    t.start()
    try:
        while True:
            with METRICS.timer(wait_timer):
                item = q.get()
            if item is DONE:
                return
            if isinstance(item, BaseException):
                raise item
            yield item
    finally:
        stop.set()


def staged_pipeline(batches: Iterator, stage: Callable, depth: int = _DEPTH):
    """Two-thread pipeline: one thread pulls (parses) batches ahead,
    a second runs `stage` (encode + H2D dispatch) — so parse of batch
    N+2 overlaps prep of batch N+1 overlaps the consumer's dispatch of
    batch N.  A single staged_prefetch serializes parse and prep on one
    thread; on scan-heavy cold paths they are comparable in cost, so
    splitting them roughly halves the critical path."""
    return staged_prefetch(
        staged_prefetch(batches, None, depth, "pipeline.scan_wait"),
        stage, depth,
    )
