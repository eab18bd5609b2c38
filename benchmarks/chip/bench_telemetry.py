"""The harness's telemetry: the program's ``ServeTelemetry`` hooks, with
each request's admission, prefill chunks and token commits timed on the
host clock the window is measured by.

The engine calls ``on_token`` right after the one ``device_get`` of each
iteration that surfaced the token, so a token's time is when the host
held it.
"""

from __future__ import annotations

import contextlib
import time

from repro.obs.serve import ServeTelemetry


class BenchTelemetry(ServeTelemetry):

    def __init__(self, annotate: bool):
        super().__init__(engine="paged")
        self.annotate = annotate
        self.clear()

    def clear(self) -> None:
        self.admitted: dict[int, float] = {}
        self.token_times: dict[int, list[float]] = {}
        self.prefill_chunks: list[tuple] = []

    def annotate_step(self, name: str, step: int):
        """The engine's ``StepTraceAnnotation`` around each jitted
        dispatch, in a traced run; the harness runs the profiler."""
        if not self.annotate:
            return contextlib.nullcontext()
        import jax.profiler
        return jax.profiler.StepTraceAnnotation(name, step_num=step)

    def on_admit(self, uid: int, prompt_len: int) -> None:
        self.admitted.setdefault(uid, time.perf_counter())
        super().on_admit(uid, prompt_len)

    def on_prefill_chunk(self, uid: int, lo: int, hi: int) -> None:
        self.prefill_chunks.append((time.perf_counter(), uid, lo, hi))
        super().on_prefill_chunk(uid, lo, hi)

    def on_token(self, uid: int) -> None:
        self.token_times.setdefault(uid, []).append(time.perf_counter())
        super().on_token(uid)
