"""The program's own spans (``repro.core.spans``), job by job, for the
per-layer metrics that read them.

The harness hands the readers no spans: each takes the program's
``recorded()``, or ``run["spans"]`` where a test gives a list of its own.
A window job is a ``server.job`` span with status ``done`` that starts at
or after the first window iteration's submit and ends at or before the
last one's return. A job's spans carry its id and start inside the
window: its ``server.queue`` span starts before its ``server.job``.
"""
from __future__ import annotations


def mean_per_job(run: dict, of) -> float | None:
    """Mean over the window jobs of ``of(root, job_spans)`` nanoseconds,
    in seconds. None when there is no iteration, when the program records
    no spans, or when no window job ran."""
    its = run["iterations"]
    if not its:
        return None
    spans = run.get("spans")
    if spans is None:
        try:
            from repro.core.spans import recorded
        except ImportError:
            return None
        spans = recorded()
    lo = min(i["t_submit"] for i in its) * 1e9
    hi = max(i["t_done"] for i in its) * 1e9
    by_job: dict = {}
    for s in spans:
        if s.job is not None and lo <= s.start_ns <= hi:
            by_job.setdefault(s.job, []).append(s)
    roots = [s for job in by_job.values() for s in job
             if s.name == "server.job" and s.attrs.get("status") == "done"
             and s.end_ns <= hi]
    if not roots:
        return None
    return sum(of(r, by_job[r.job]) for r in roots) / len(roots) / 1e9


def durations(spans: list, *names: str) -> int:
    return sum(s.end_ns - s.start_ns for s in spans if s.name in names)


def union(spans: list, *names: str) -> int:
    """Nanoseconds covered by at least one span of ``names``."""
    total, reach = 0, None
    for s, e in sorted((s.start_ns, s.end_ns) for s in spans
                       if s.name in names):
        s = s if reach is None else max(s, reach)
        if e > s:
            total += e - s
            reach = e
    return total


def self_time(spans: list, *names: str) -> int:
    """Summed self time of the spans of ``names``: each one's duration
    minus the union of its direct children's intervals, clipped to it."""
    from repro.core.spans import self_ns
    return sum(self_ns(s, spans) for s in spans if s.name in names)
