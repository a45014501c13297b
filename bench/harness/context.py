"""What a metric reader is given, and the arithmetic several share."""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional

from harness.tracing import Trace


@dataclass
class Record:
    """One invocation of the window, on the host's monotonic clock."""
    fn: int
    due: float
    release: float
    completion: Optional[float]
    queue_time: Optional[float]
    overhead: float
    service_time: float
    start_type: str
    failed: bool

    @property
    def latency(self) -> Optional[float]:
        """Due time to completion: a stall delays what is due after it."""
        return None if self.completion is None else self.completion - self.due


@dataclass
class Context:
    records: List[Record]
    window: tuple               # (open, close), host monotonic seconds
    setup_s: float
    request: Dict[str, int]     # batch, prompt, new_tokens
    work: Dict[str, float]      # reference.<family>.work of the request
    peak: Dict[str, float]      # bf16_flops, hbm_bytes_s of the device
    trace: Optional[Trace] = None
    extra: Dict[str, float] = field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return self.window[1] - self.window[0]

    def done(self) -> List[Record]:
        return [r for r in self.records
                if r.completion is not None and not r.failed]

    @property
    def flops_per_invocation(self) -> float:
        w, q = self.work, self.request
        return w["prefill_flops"] + q["batch"] * q["new_tokens"] * w["token_flops"]


def roofline(ctx: Context, kind: str) -> Optional[float]:
    """Per cent of the roofline the ``kind`` ("prefill" or "decode")
    programs reach over the traced ``execute`` spans: the least time the
    chip needs for their work (the larger of operations over peak and
    bytes over bandwidth; decode counted per token), over the device time
    of modules whose name holds ``kind`` inside those spans."""
    if ctx.trace is None:
        return None
    spans = ctx.trace.executes()
    t = ctx.trace.module_time(kind, spans)
    if not spans or t <= 0:
        return None
    w, q = ctx.work, ctx.request
    if kind == "prefill":
        n, flops, nbytes = len(spans), w["prefill_flops"], w["prefill_bytes"]
    else:
        n = len(spans) * q["batch"] * q["new_tokens"]
        flops, nbytes = w["token_flops"], w["token_bytes"]
    bound = n * max(flops / ctx.peak["bf16_flops"],
                    nbytes / ctx.peak["hbm_bytes_s"])
    return 100.0 * bound / t


def mfu(ctx: Context) -> Optional[float]:
    """Per cent of the chip's bf16 peak that the model operations of the
    ``execute`` spans inside the traced window make, over the window."""
    if ctx.trace is None or not ctx.trace.executes():
        return None
    flops = len(ctx.trace.executes()) * ctx.flops_per_invocation
    n_chips = max(len(ctx.trace.devices), 1)
    return 100.0 * flops / (ctx.trace.window_s * n_chips
                            * ctx.peak["bf16_flops"])


def idle_share(ctx: Context) -> Optional[float]:
    """Per cent of the traced window in which no XLA module ran on the
    chip."""
    t = ctx.trace
    if t is None or t.window_s <= 0 or not t.modules:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
