"""Wall-clock stack sampler for the traced run: every ``interval``
seconds, the stack of every thread that is executing a build (inside
``run_build`` and past the admission queue), as "function (file.py)"
labels from the outermost frame in. Only ``--trace 1`` starts it."""

from __future__ import annotations

import os
import sys
import threading
import time

_BUILD_ENTRY = "run_build (server.py)"
_QUEUED = "acquire (server.py)"


def _stack(frame) -> list[str]:
    labels = []
    while frame is not None:
        code = frame.f_code
        labels.append(f"{code.co_name} ({os.path.basename(code.co_filename)})")
        frame = frame.f_back
    labels.reverse()
    return labels


class Sampler:
    def __init__(self, interval: float = 0.02) -> None:
        self.interval = interval
        # [(monotonic seconds, [stack, ...] of the building threads)]
        self.samples: list[tuple[float, list[list[str]]]] = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="perfbench-sampler")

    def start(self) -> "Sampler":
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        self._thread.join()

    def _run(self) -> None:
        me = threading.get_ident()
        while not self._stop.wait(self.interval):
            now = time.monotonic()
            stacks = []
            for ident, frame in sys._current_frames().items():
                if ident == me:
                    continue
                stack = _stack(frame)
                if _BUILD_ENTRY in stack and _QUEUED not in stack:
                    stacks.append(stack)
            self.samples.append((now, stacks))

    def window(self, t_open: float, t_close: float):
        return [(t, stacks) for t, stacks in self.samples
                if t_open <= t <= t_close]


def share_under(samples, predicate) -> float | None:
    """Of all sampled stacks of building threads, the percentage with a
    frame for which ``predicate(label)`` holds."""
    total = hits = 0
    for _, stacks in samples:
        for stack in stacks:
            total += 1
            if any(predicate(label) for label in stack):
                hits += 1
    if total == 0:
        return None
    return 100.0 * hits / total


def seconds_by_frame(samples, t_from: float, t_to: float, top: int = 7):
    """[(innermost frame, seconds)] of the building threads between two
    monotonic seconds: each pass's interval shared among the frames it
    saw."""
    frames: dict = {}
    passes = 0
    for t, stacks in samples:
        if t_from <= t <= t_to:
            passes += 1
            for stack in stacks:
                frames[stack[-1]] = frames.get(stack[-1], 0) + 1
    scale = (t_to - t_from) / max(passes, 1)
    ranked = sorted(frames.items(), key=lambda kv: -kv[1])[:top]
    return [(label, count * scale) for label, count in ranked]
