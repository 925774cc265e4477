"""Prometheus text, parsed to {(name, labels): value}, and deltas of it."""

from __future__ import annotations

import re

_SAMPLE = re.compile(r"^([a-zA-Z_:][\w:]*)(\{[^}]*\})? (\S+)$", re.MULTILINE)


def parse(text: str) -> dict[tuple[str, str], float]:
    out = {}
    for name, labels, value in _SAMPLE.findall(text):
        try:
            out[(name, labels)] = float(value)
        except ValueError:
            continue
    return out


def total(samples: dict, name: str) -> float | None:
    """Sum of a metric over its label sets; None if it is not exposed."""
    values = [v for (n, _), v in samples.items() if n == name]
    return sum(values) if values else None


def delta(before: dict, after: dict, name: str) -> float | None:
    """after - before of a counter summed over labels. A label set that is
    new in `after` started from zero."""
    b, a = total(before, name), total(after, name)
    if a is None:
        return None
    return a - (b or 0.0)


def delta_over(scrapes: list[tuple[dict, dict]], name: str) -> float | None:
    """The delta summed over several processes' (before, after) scrapes."""
    parts = [delta(b, a, name) for b, a in scrapes]
    parts = [p for p in parts if p is not None]
    return sum(parts) if parts else None
