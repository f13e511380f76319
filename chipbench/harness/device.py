"""The device a run is allowed on, its peaks, its memory, and a count of the
programs JAX builds while the window is open."""

import json
import os
from typing import Any, Dict

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Refused(SystemExit):
    """The run may not start: exits non-zero before any result line."""

    def __init__(self, why: str) -> None:
        super().__init__(f"chipbench: {why}")


def load_peaks() -> Dict[str, Any]:
    with open(os.path.join(HERE, "peaks.json")) as f:
        return json.load(f)


def require(chips: int) -> Dict[str, Any]:
    """The attached device as JAX reports it, with its row of the peaks
    table.  Refuses anything but ``chips`` TPU devices of a listed kind."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"needs a TPU; JAX's first device is "
                      f"{devs[0].platform}:{devs[0].device_kind}")
    if len(devs) != chips:
        raise Refused(f"the cell asks for {chips} chip(s); JAX sees "
                      f"{len(devs)}")
    peaks = load_peaks()
    kind = devs[0].device_kind
    if kind not in peaks:
        raise Refused(f"device kind {kind!r} is not in chipbench/peaks.json "
                      f"(listed: {sorted(peaks)})")
    return {"platform": devs[0].platform, "kind": kind, "count": len(devs),
            "peaks": peaks[kind]}


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip, over the process's life."""
    import jax

    return max(int(d.memory_stats()["peak_bytes_in_use"])
               for d in jax.devices())


class CompileCounter:
    """Counts programs built (compiled, or fetched from the persistent cache)
    between ``open`` and ``close``: inside a window there must be none."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        from jax import monitoring

        self._open = False
        self.count = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if self._open and event == self.EVENT:
            self.count += 1

    def open(self) -> None:
        self._open = True

    def close(self) -> int:
        self._open = False
        return self.count
