"""The client side: offers requests to the engine and stamps their tokens.

Open loop: each request is passed to ``NanoCPEngine.add_request`` once its
due time has passed, between ``step()`` calls; after every ``step()``
returns, each token that became visible is stamped on the client's clock.
Nothing the program records about time is used for the end-to-end
metrics.  Each call into the program is wrapped in a
``jax.profiler.TraceAnnotation`` (``chipbench.add_request``,
``chipbench.step``, ``chipbench.wait``), so a trace can say what the host
was doing while the device sat idle.

Per step the client also keeps the program's own spans
(``engine.timings``: ``step_us``, ``prefill_us``, ``harvest_us`` ...) and
the rows decoding after it with their context lengths, for the per-layer
metrics.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax

SPAN_KEYS = ("step_us", "prefill_us", "harvest_us", "lower_us",
             "dispatch_us")


@dataclass
class Tracked:
    rid: int
    due: float                  # absolute, client clock
    prompt_len: int
    max_new_tokens: int
    in_window: bool
    stamps: list = field(default_factory=list)


@dataclass
class StepRecord:
    rows: int                   # rows decoding after the step
    ctx_list: tuple             # each row's context length
    spans: dict                 # engine.timings of this step (us)
    prefilled: tuple            # prompt lengths whose first token came out
    traced: bool = False


class Client:
    def __init__(self, eng):
        self.eng = eng
        self.tracked: dict = {}         # rid -> Tracked
        self.open: dict = {}            # rid -> Tracked, not yet finished
        self.steps: list = []
        self.lateness: list = []
        self.tracing = False

    # -------------------------------------------------------------- requests
    def add(self, prompt, max_new_tokens: int, due: float,
            in_window: bool) -> Tracked:
        now = time.perf_counter()
        with jax.profiler.TraceAnnotation("chipbench.add_request"):
            rid = self.eng.add_request(prompt, max_new_tokens)
        tr = Tracked(rid, due, len(prompt), max_new_tokens, in_window)
        self.tracked[rid] = tr
        self.open[rid] = tr
        self.lateness.append(now - due)
        return tr

    # ----------------------------------------------------------------- steps
    def step(self) -> None:
        with jax.profiler.TraceAnnotation("chipbench.step"):
            self.eng.step()
        t = time.perf_counter()
        prefilled = []
        results = self.eng.results
        for rid in list(self.open):
            tr = self.open[rid]
            n = len(results[rid].tokens)
            if n > len(tr.stamps):
                if not tr.stamps:
                    prefilled.append(tr.prompt_len)
                tr.stamps.extend([t] * (n - len(tr.stamps)))
            if n >= tr.max_new_tokens:
                del self.open[rid]
        ctx = self._dispatched_rows()
        self.steps.append(StepRecord(
            len(ctx), tuple(ctx),
            {k: self.eng.timings.get(k, 0.0) for k in SPAN_KEYS},
            tuple(prefilled), self.tracing))

    def _dispatched_rows(self) -> list:
        """Context length of each row still decoding after the step (a row
        that the step finished by length is not counted)."""
        return [req.prompt_len + req.generated
                for req in self.eng.cluster.active.values()]

    def busy(self) -> bool:
        return bool(self.open)

    def wait_until(self, t: float) -> None:
        with jax.profiler.TraceAnnotation("chipbench.wait"):
            while True:
                left = t - time.perf_counter()
                if left <= 0:
                    return
                time.sleep(min(left, 0.002))

    def sync(self) -> None:
        """Wait until the device has finished what was dispatched."""
        jax.block_until_ready(self.eng.state)
