"""HVD005 — observability name tables (the PR-4 counter-name lint,
ported into the framework; ``tools/check_counter_names.py`` is now a
shim over this checker plus HVD004).

Dashboards and the timeline-summary tool key on three name families —
Chrome-trace counter activities (``timeline.counter("track", "SCHED",
{...})``), registry metric names (``metrics.counter("monitor.scrapes")``
etc.), and the event-log lifecycle kinds — all declared once in
:mod:`horovod_tpu.metrics` (``TIMELINE_COUNTER_SERIES``,
``METRIC_HELP``, ``LIFECYCLE_EVENT_COUNTERS``).  Membership is checked
BOTH ways: an unregistered name in code fails (a dashboard would
silently miss it) and a registered name with no call site fails (dead
table entries rot).  Composed-name families (``"serve." + key`` over
the LIFECYCLE series, ``"prefix." + key`` over PREFIX) have no literal
call site and are excused from the dead-entry direction.

Fault-site membership, previously part of the same script, lives in
HVD004 now.
"""

from __future__ import annotations

import re
from typing import Iterator

from tools.hvdlint.core import Checker, Finding, Project, register

# timeline.counter("<track>", "<ACTIVITY>", {...}) — the uppercase
# second string argument distinguishes a Chrome-trace counter emission
# from MetricsRegistry.counter(name) lookups.
_TIMELINE_COUNTER = re.compile(
    r"\.counter\(\s*[\"']([^\"']+)[\"']\s*,\s*[\"']([A-Z][A-Z_]*)[\"']")
_SERIES_KEY = re.compile(r"[\"']([a-z_]+)[\"']\s*:")
# registry.counter/gauge/histogram("<name>"...) with a LITERAL name —
# the closing quote must be followed by `,` or `)` so composed names
# ("serve." + key) and f-strings stay out of scope.
_REGISTRY_METRIC = re.compile(
    r"\.(counter|gauge|histogram)\(\s*[\"']([a-z0-9_.]+)[\"']\s*[,)]")
_ACTIVITY_NEXT = re.compile(r"\s*[\"'][A-Z]")


def _scan(files) -> tuple[dict[str, set], dict[str, tuple[str, int]],
                          dict[str, tuple[str, int]]]:
    """Returns (activity -> literal series keys,
    activity -> first emission site, metric name -> first site)."""
    activities: dict[str, set] = {}
    act_sites: dict[str, tuple[str, int]] = {}
    metric_sites: dict[str, tuple[str, int]] = {}
    for sf in files:
        text = sf.text
        line_of = lambda pos: text.count("\n", 0, pos) + 1  # noqa: E731
        for m in _TIMELINE_COUNTER.finditer(text):
            activity = m.group(2)
            act_sites.setdefault(activity, (sf.rel, line_of(m.start())))
            keys = activities.setdefault(activity, set())
            # Only dict *literals* contribute keys (dict(self.counters)
            # style emissions are covered by the table itself).
            window = text[m.end():m.end() + 400]
            depth_end = window.find(")")
            keys.update(_SERIES_KEY.findall(
                window if depth_end < 0 else window[:depth_end + 1]))
        for m in _REGISTRY_METRIC.finditer(text):
            if _ACTIVITY_NEXT.match(text, m.end()):
                continue             # a timeline.counter(track, "SCHED"
            metric_sites.setdefault(m.group(2),
                                    (sf.rel, line_of(m.start())))
    return activities, act_sites, metric_sites


@register
class CounterNameChecker(Checker):
    code = "HVD005"
    summary = ("observability name not in its canonical table "
               "(TIMELINE_COUNTER_SERIES / METRIC_HELP / "
               "LIFECYCLE_EVENT_COUNTERS), or a dead table entry")

    def check(self, project: Project) -> Iterator[Finding]:
        activities, act_sites, metric_sites = _scan(project.files)
        series = project.timeline_counter_series
        metrics_rel = project.METRICS_FILE

        registered = set(series)
        for activity in sorted(activities):
            rel, line = act_sites[activity]
            if activity not in registered:
                yield Finding(
                    self.code, rel, line,
                    f"timeline counter activity `{activity}` is emitted "
                    "but not registered in "
                    "metrics.TIMELINE_COUNTER_SERIES",
                    symbol=f"{activity}:unregistered-activity")
                continue
            extra = activities[activity] - set(series[activity])
            if extra:
                yield Finding(
                    self.code, rel, line,
                    f"timeline counter `{activity}` emits series "
                    f"{sorted(extra)} not registered in "
                    f"metrics.TIMELINE_COUNTER_SERIES[{activity!r}]",
                    symbol=f"{activity}:unregistered-series")
        for activity in sorted(registered - set(activities)):
            yield Finding(
                self.code, metrics_rel,
                project.line_of(metrics_rel, f'"{activity}"'),
                f"metrics.TIMELINE_COUNTER_SERIES registers "
                f"`{activity}` but no timeline.counter call emits it",
                symbol=f"{activity}:dead-activity")

        # Registry metric names vs METRIC_HELP, both directions.
        help_names = set(project.metric_help)
        dynamic = (
            {"serve." + k for k in series.get("LIFECYCLE", ())}
            | {"prefix." + k for k in series.get("PREFIX", ())}
            # Per-endpoint scrape instruments: emitted as
            # monitor.scrape_s.<endpoint> f-strings, documented under
            # the family base name.
            | {"monitor.scrape_s", "monitor.scrape_errors",
               # one gauge per held expert: moe.held_load.<expert>
               "moe.held_load"})
        for name in sorted(set(metric_sites) - help_names):
            rel, line = metric_sites[name]
            yield Finding(
                self.code, rel, line,
                f"registry metric `{name}` is emitted but has no "
                "metrics.METRIC_HELP entry (dashboards get no "
                "# HELP line)",
                symbol=f"{name}:no-help")
        for name in sorted(help_names - set(metric_sites) - dynamic):
            yield Finding(
                self.code, metrics_rel,
                project.line_of(metrics_rel, f'"{name}"'),
                f"metrics.METRIC_HELP describes `{name}` but no "
                "counter/gauge/histogram call site emits it",
                symbol=f"{name}:dead-help")

        # Internal consistency: the event-log replay map must cover
        # exactly the LIFECYCLE counter series.
        lifecycle = set(series.get("LIFECYCLE", ()))
        mapped = set(project.lifecycle_event_counters.values())
        if lifecycle != mapped:
            yield Finding(
                self.code, metrics_rel,
                project.line_of(metrics_rel, "LIFECYCLE_EVENT_COUNTERS"),
                f"LIFECYCLE_EVENT_COUNTERS values {sorted(mapped)} != "
                f"LIFECYCLE series {sorted(lifecycle)}",
                symbol="lifecycle-map:mismatch")
