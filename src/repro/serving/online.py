"""Online (submit-while-running) serving front end.

:class:`OnlineLLM` turns the pull-based offline engine into a live
service: ``submit()`` may be called at any time — including while the
engine loop is mid-drain — and returns a :class:`RequestStream` that
yields tokens *per engine tick*, not after the batch finishes.  The
continuous-batching admission already supports joining a live loop (the
queue is drained into free slots every tick), so the front end is pure
orchestration: an admission inbox, a per-request delivery cursor, and an
optional background pump thread.

Two pump modes, one delivery surface:

* **cooperative** (default): no thread.  A consumer blocking on
  ``stream.next_event()`` drives ``OnlineLLM.step()`` inline until its
  event arrives — single-threaded, deterministic, what the tests and the
  Poisson bench use.
* **threaded**: ``start()`` launches a daemon pump; ``submit()`` from any
  thread wakes it, consumers block on a condition variable.  ``close()``
  stops the pump.  (An ``async for`` adapter rides on top via
  ``RequestStream.__aiter__`` — the blocking ``next_event`` runs in the
  event loop's default executor.)

Token streams are **bit-identical to offline** ``LLM.generate``: every
token is a function of ``(seed, request_id, token_idx)`` only, so
arrival timing, admission order, and prefix-cache hits change *when* a
token is delivered, never *which* token.

Latency accounting: each :class:`StreamEvent` is stamped when the pump
books it (serving-side delivery, the number an operator's SLO sees);
``RequestStream.ttft_s`` / ``inter_token_s()`` derive p50/p99-able
samples from those stamps.  The engine additionally stamps
``SequenceState.first_token_time`` when the token is *sampled*.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Union

from repro import obs
from repro.serving.llm import LLM, EngineConfig, RequestOutput
from repro.serving.request import SamplingParams, SequenceState, Status

__all__ = ["OnlineLLM", "RequestStream", "StreamEvent"]


@dataclass(frozen=True)
class StreamEvent:
    """One delivered token of one online request."""
    request_id: int
    index: int                  # token index in the generated stream
    token: int
    time: float                 # perf_counter at delivery (pump-side)
    finished: bool = False      # True on the request's last token
    finish_reason: Optional[str] = None


class RequestStream:
    """Per-request token stream handed back by :meth:`OnlineLLM.submit`.

    Iterate it (sync ``for`` or ``async for``) or call
    :meth:`next_event` directly; ``None``/StopIteration marks the end of
    the stream.  With no pump thread running, the consumer itself steps
    the engine (cooperative mode)."""

    def __init__(self, online: "OnlineLLM", request_id: int,
                 prompt: List[int]):
        self._online = online
        self.request_id = request_id
        self.prompt = prompt
        self.seq: Optional[SequenceState] = None    # bound at admission
        self.submit_time = time.perf_counter()
        self._events: deque = deque()
        self._cv = threading.Condition()
        self._closed = False
        self._tokens: List[int] = []
        self._event_times: List[float] = []
        self.first_token_time: Optional[float] = None

    # -- producer side (pump) -------------------------------------------

    def _push(self, ev: StreamEvent) -> None:
        with self._cv:
            if self.first_token_time is None:
                self.first_token_time = ev.time
            self._event_times.append(ev.time)
            self._tokens.append(ev.token)
            self._events.append(ev)
            if ev.finished:
                self._closed = True
            self._cv.notify_all()

    # -- consumer side ----------------------------------------------------

    def next_event(self, timeout: Optional[float] = None
                   ) -> Optional[StreamEvent]:
        """Next :class:`StreamEvent`, or ``None`` when the stream is
        complete.  Blocks (threaded pump) or steps the engine inline
        (cooperative mode) until one is available."""
        deadline = None if timeout is None else \
            time.perf_counter() + timeout
        while True:
            with self._cv:
                if self._events:
                    return self._events.popleft()
                if self._closed:
                    return None
                if self._online._thread is not None:
                    wait = 0.1 if deadline is None else \
                        deadline - time.perf_counter()
                    if wait <= 0 or not self._cv.wait(timeout=wait):
                        if deadline is not None and \
                                time.perf_counter() >= deadline:
                            raise TimeoutError(
                                f"request {self.request_id}: no token "
                                f"within {timeout}s")
                    continue
            # cooperative: drive the shared engine until our event lands
            if not self._online.step():
                with self._cv:
                    if self._events or self._closed:
                        continue
                raise RuntimeError(
                    f"request {self.request_id}: engine drained with the "
                    "stream still open (was the engine aborted?)")

    def __iter__(self):
        while True:
            ev = self.next_event()
            if ev is None:
                return
            yield ev

    def __aiter__(self):
        return self._agen()

    async def _agen(self):
        import asyncio
        loop = asyncio.get_running_loop()
        while True:
            ev = await loop.run_in_executor(None, self.next_event)
            if ev is None:
                return
            yield ev

    # -- results / metrics ------------------------------------------------

    @property
    def finished(self) -> bool:
        with self._cv:
            return self._closed and not self._events

    def tokens(self) -> List[int]:
        """Tokens delivered so far (grows as the stream advances)."""
        with self._cv:
            return list(self._tokens)

    def result(self) -> RequestOutput:
        """Drain the stream to completion and return the final
        :class:`RequestOutput` — the online counterpart of
        ``LLM.generate``'s return value."""
        for _ in self:
            pass
        assert self.seq is not None
        # the engine reaps a finished sequence on the tick AFTER its last
        # token (freeing the slot + stamping finish_time/status); make
        # sure that bookkeeping ran before snapshotting the output
        while self.seq.status is not Status.FINISHED and self._online.step():
            pass
        return RequestOutput.from_seq(
            self.seq,
            trace=self._online.engine.request_trace(self.request_id))

    @property
    def ttft_s(self) -> Optional[float]:
        """Submit-to-first-delivered-token, pump-side (None until then)."""
        if self.first_token_time is None:
            return None
        return self.first_token_time - self.submit_time

    def inter_token_s(self) -> List[float]:
        """Deltas between consecutive delivery stamps (ITL samples)."""
        with self._cv:
            ts = list(self._event_times)
        return [b - a for a, b in zip(ts, ts[1:])]


class OnlineLLM:
    """Submit-while-running front end over :class:`repro.serving.llm.LLM`.

        online = OnlineLLM("yi-9b", config=EngineConfig(prefix_cache=True))
        s1 = online.submit(prompt_a)            # joins the live loop
        s2 = online.submit(prompt_b)            # ... at any time
        for ev in s1:                           # tokens per tick
            print(ev.token, ev.finished)
        out = s2.result()                       # drain to a RequestOutput

    Pass ``llm=`` to wrap an existing engine instead of building one.
    Thread-safe: ``submit`` may be called from any thread; engine
    stepping is serialised by an internal lock."""

    def __init__(self, model=None, *,
                 config: Optional[EngineConfig] = None, params=None,
                 rt=None, reduced: bool = True,
                 llm: Optional[LLM] = None):
        if llm is None:
            if model is None:
                raise ValueError("OnlineLLM needs a model (arch name / "
                                 "ModelConfig) or an existing llm=")
            llm = LLM(model, config=config, params=params, rt=rt,
                      reduced=reduced)
        self.llm = llm
        self.engine = llm.engine
        self._inbox: deque = deque()            # (Request, RequestStream)
        self._streams: Dict[int, RequestStream] = {}
        self._delivered: Dict[int, int] = {}
        self._lock = threading.Lock()           # inbox + stream registry
        self._step_lock = threading.Lock()      # serialises engine access
        self._wake = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._stop = False

    # -- submission --------------------------------------------------------

    def submit(self, prompt: Sequence[int],
               sampling: Union[SamplingParams, None] = None
               ) -> RequestStream:
        """Enqueue one prompt into the live loop; returns its stream.
        Request ids are assigned in submission order (the same counter as
        ``LLM.generate``), so a given arrival order reproduces the exact
        offline token streams."""
        with self._lock:
            req = self.llm._make_requests(
                [prompt], sampling if sampling is None else [sampling])[0]
            stream = RequestStream(self, req.request_id, req.prompt)
            self._inbox.append((req, stream))
            self._streams[req.request_id] = stream
            self._delivered[req.request_id] = 0
        self._wake.set()
        return stream

    # -- pump --------------------------------------------------------------

    def step(self) -> bool:
        """One pump iteration: admit queued submissions, advance the
        engine one tick, deliver newly generated tokens to their streams.
        Returns True while any work remains."""
        rec = self.engine.recorder
        with self._step_lock:
            with obs.span("front.inbox", rec):
                self._drain_inbox()
            alive = self.engine.step()
            with obs.span("front.deliver", rec):
                self._dispatch()
        with self._lock:
            return alive or bool(self._inbox)

    def run_until_idle(self, max_steps: int = 100_000) -> int:
        """Cooperative drain (no thread): step until nothing is pending.
        Returns the number of steps taken."""
        steps = 0
        while steps < max_steps and self.step():
            steps += 1
        return steps

    def _drain_inbox(self) -> None:
        with self._lock:
            items = list(self._inbox)
            self._inbox.clear()
        rec = self.engine.recorder
        for req, stream in items:
            stream.seq = self.engine.submit([req])[0]
            if rec is not None:
                # the stream's own submit stamp — the float its ttft_s
                # subtracts — so trace TTFT matches the stream bitwise
                rec.request_stream_submit(req.request_id,
                                          stream.submit_time)

    def _dispatch(self) -> None:
        now = time.perf_counter()
        rec = self.engine.recorder
        with self._lock:
            live = list(self._streams.items())
        done: List[int] = []
        for rid, stream in live:
            seq = stream.seq
            if seq is None:
                continue
            n = len(seq.generated)
            d = self._delivered[rid]
            if d >= n:
                continue
            if rec is not None:
                # every event pushed this tick carries the same ``now``
                # stamp, so recording it once per request keeps the trace
                # delivery times bitwise equal to the stream's
                rec.request_delivery(rid, now, n - d)
            fin = seq.is_done()
            reason = seq.finish_reason()
            while d < n:
                last = fin and d == n - 1
                stream._push(StreamEvent(
                    request_id=rid, index=d, token=seq.generated[d],
                    time=now, finished=last,
                    finish_reason=reason.value if last and reason else None))
                d += 1
            self._delivered[rid] = d
            if fin:
                done.append(rid)
        if done:
            with self._lock:
                for rid in done:
                    self._streams.pop(rid, None)
                    self._delivered.pop(rid, None)

    # -- threaded pump -----------------------------------------------------

    def start(self) -> "OnlineLLM":
        """Launch the background pump thread.  Consumers then block on
        delivery instead of stepping the engine themselves."""
        if self._thread is None:
            self._stop = False
            self._thread = threading.Thread(
                target=self._pump, name="online-pump", daemon=True)
            self._thread.start()
        return self

    def _pump(self) -> None:
        while not self._stop:
            if not self.step():
                # idle: sleep until a submit wakes us (short timeout so
                # close() is prompt even without a wake)
                self._wake.clear()
                self._wake.wait(timeout=0.05)

    def close(self, timeout: float = 10.0) -> None:
        """Stop the pump thread (no-op in cooperative mode)."""
        self._stop = True
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout=timeout)
            self._thread = None

    def __enter__(self) -> "OnlineLLM":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- reporting ---------------------------------------------------------

    def stats(self) -> dict:
        return self.engine.throughput_report()
