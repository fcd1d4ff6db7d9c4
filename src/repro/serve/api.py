"""Public request/response types for the serve engine.

The engine grew up around bare ints and raw token lists: ``submit(prompt,
max_new_tokens)`` returned a request id and ``run()`` returned
``{rid: [token, ...]}``.  That surface can't carry what a long-lived server
needs — per-request timing, finish reasons, prefix-cache provenance, or a
stream callback — so this module defines the typed API:

* :class:`Request` — what a caller wants generated (prompt, budget, optional
  per-token stream callback).  ``Engine.submit(Request)`` returns a
  :class:`RequestHandle`.
* :class:`StreamEvent` — one token (or the terminal event) delivered to a
  request's ``stream`` callback at each decode-chunk boundary.
* :class:`GenerationResult` — the finished request: tokens, finish reason,
  TTFT / throughput, and how much of the prompt was served from the prefix
  cache.
* :class:`RequestHandle` — a future for one request; ``result()`` blocks
  until the engine drains it (the :class:`repro.serve.server.Server` resolves
  handles from its worker thread).

The legacy positional ``submit(prompt, max_new_tokens)`` / dict-of-tokens
``run()`` surface still works behind a one-per-process
``DeprecationWarning`` (see ``docs/SERVING.md`` for migration notes).

Everything here is host-side and jax-free.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Callable, List, Optional, Sequence

#: finish reasons carried by GenerationResult / terminal StreamEvent
FINISH_STOP = "stop"        # the EOS token was emitted
FINISH_LENGTH = "length"    # the max_new_tokens budget was exhausted

#: prefix-cache provenance values (``None`` on GenerationResult = cold)
PREFIX_HIT_FULL = "full"        # whole prompt served from cache, no prefill
PREFIX_HIT_PARTIAL = "partial"  # page-aligned prefix shared, prefill re-run


@dataclasses.dataclass(frozen=True)
class StreamEvent:
    """One streamed token (or the terminal event) for a request.

    Token events arrive in order with ``finished=False`` as each decode
    chunk reaches the host; the terminal event carries ``token=None``,
    ``finished=True`` and the finish reason.  ``index`` is the token's
    position in the generated sequence (== count of tokens delivered so
    far for the terminal event).
    """
    request_id: int
    token: Optional[int]
    index: int
    finished: bool = False
    finish_reason: Optional[str] = None


@dataclasses.dataclass(frozen=True)
class Request:
    """One generation request for :meth:`Engine.submit`.

    Args:
      prompt: non-empty token-id sequence.
      max_new_tokens: decode budget (>= 1).
      row: index of this request in the ``extra_inputs`` arrays later
        passed to ``run()`` (required when extras are used; ``generate``
        fills it automatically).
      stream: optional callback invoked with a :class:`StreamEvent` per
        generated token plus one terminal event.  Called from the thread
        driving the engine (the server's worker thread in server mode).
      temperature: optional sampling-temperature assertion.  The engine is
        compiled against one ``ServeConfig.temperature``; a Request that
        names a different one is rejected at submit instead of silently
        sampling at the wrong temperature.
    """
    prompt: Sequence[int]
    max_new_tokens: int
    row: Optional[int] = None
    stream: Optional[Callable[[StreamEvent], None]] = None
    temperature: Optional[float] = None


@dataclasses.dataclass(frozen=True)
class GenerationResult:
    """One finished request, as returned by ``Engine.run()``.

    ``tokens`` matches the legacy raw-token return exactly (the EOS token,
    when hit, is included).  ``ttft_s`` is submit-to-first-token-host-
    visible (through a ``Server``, from ``Server.submit``); ``tok_per_s``
    is ``len(tokens) / total_s``.  ``prefix_hit`` is
    ``"full"`` / ``"partial"`` / ``None`` with ``cached_prefix_tokens``
    counting the prompt tokens served from the prefix cache.
    """
    request_id: int
    tokens: List[int]
    finish_reason: str
    prompt_len: int
    ttft_s: Optional[float]
    total_s: float
    tok_per_s: float
    prefix_hit: Optional[str] = None
    cached_prefix_tokens: int = 0


class RequestHandle:
    """Future for one submitted :class:`Request`.

    The engine resolves the handle the moment the request finishes (not at
    the end of the drain), so server-mode callers see results at request
    granularity.  ``result()`` re-raises the engine's exception when the
    drain died under the request.
    """

    def __init__(self, request_id: int = -1):
        self.request_id = request_id
        self._done = threading.Event()
        self._result: Optional[GenerationResult] = None
        self._error: Optional[BaseException] = None

    # -- engine side ----------------------------------------------------
    def _set_result(self, result: GenerationResult) -> None:
        self._result = result
        self._done.set()

    def _set_error(self, exc: BaseException) -> None:
        self._error = exc
        self._done.set()

    # -- caller side ----------------------------------------------------
    @property
    def done(self) -> bool:
        return self._done.is_set()

    def result(self, timeout: Optional[float] = None) -> GenerationResult:
        if not self._done.wait(timeout):
            raise TimeoutError(
                f"request {self.request_id} not finished after {timeout}s")
        if self._error is not None:
            raise self._error
        assert self._result is not None
        return self._result
