"""The generic per-layer readers, and the context they read from.

`ctx` is what one traced run leaves behind:
    ctx["scrape_a"], ctx["scrape_b"]   parsed Prometheus scrapes at the
                                        window's two ends
    ctx["drives"], ctx["workers"]
    ctx["trace"]                        benchmark/trace.py's reduction of
                                        the interval between the trace's
                                        two marks, some seconds inside
                                        the window
    ctx["config"], ctx["peaks"]
    ctx["payload_mib_s"]                {op: MiB/s of payload the clients
                                        moved inside the window}
    ctx["payload_by_key"]               {op: {key: payload bytes of that
                                        key's operations inside the
                                        window}}, by the clients' own log
    ctx["notes"]                        what a reader wants said beside
                                        its number (result line, `cell`)

The time between two scrapes is the server's own: the difference of
`minio_tpu_process_uptime_seconds`, which it reads while it renders
the counters. Under 32 streaming PUTs one scrape takes seconds to
come back, so the host's clock at the send or at the answer says
little about when the counters were read.

A reader that finds nothing to read returns None and the metric is
left out of the line; it never returns 0 for a share of a peak. One
that finds work counted and no program to hold it against raises.
"""

from __future__ import annotations

import re

from benchmark import work


def series_sum(scrape: dict, name: str, labels: dict | None = None) -> float:
    """Sum of the series of `name` whose labels match; a label's value
    may be one string or a list of allowed ones."""
    want = {k: ([v] if isinstance(v, str) else list(v))
            for k, v in (labels or {}).items()}
    total = 0.0
    for key, value in scrape.get(name, {}).items():
        have = dict(key)
        if all(have.get(k) in vs for k, vs in want.items()):
            total += value
    return total


UPTIME = "minio_tpu_process_uptime_seconds"


def scraped_seconds(a: dict, b: dict) -> float:
    return series_sum(b, UPTIME) - series_sum(a, UPTIME)


def _delta(a: dict, b: dict, terms: list[dict]) -> float:
    return sum(series_sum(b, t["series"], t.get("labels"))
               - series_sum(a, t["series"], t.get("labels")) for t in terms)


def prometheus_delta(ctx: dict, spec: dict, a_key: str = "scrape_a",
                     b_key: str = "scrape_b"):
    """(delta of numerator series) / (delta of denominator series, or a
    named span of the run) * scale."""
    a, b = ctx.get(a_key), ctx.get(b_key)
    if a is None or b is None:
        return None
    num = _delta(a, b, spec["numerator"])
    den_spec = spec["denominator"]
    if isinstance(den_spec, list):
        den = _delta(a, b, den_spec)
    else:
        window_s = scraped_seconds(a, b)
        den = {"window_s": window_s,
               "window_s*drives": window_s * ctx["drives"],
               "window_s*workers": window_s * ctx["workers"]}[den_spec]
    if den <= 0:
        return None
    return num / den * spec.get("scale", 1.0)


def device_trace(ctx: dict, spec: dict):
    """From the device trace, over the interval between its two marks:
    `idle_share`, or a kernel family's `roofline_share`: the least time
    per second the chip could take for the useful work (work.py) over
    the device time per second of every op of the programs (`XLA
    Modules`) whose name matches `modules` and that started between the
    marks. The work per second is the payload the clients moved in the
    window (their own log: one erasure block per `erasure_block_bytes`
    of `op`) times the share of it that rode the device (`on_device`,
    a ratio of counters). Two rates of one steady window, each exact
    over its own interval; no scrape's timing is in either (a scrape
    takes seconds under load, and its counters are read who knows when
    inside them)."""
    tr = ctx.get("trace")
    if not tr or not tr.get("chips") or tr.get("window_s", 0) <= 0:
        return None
    if spec["quantity"] == "idle_share":
        return (1.0 - tr["busy_s"] / tr["window_s"]) * 100.0
    if spec["quantity"] == "roofline_share":
        share = prometheus_delta(ctx, spec["on_device"])
        rate = ctx["payload_mib_s"].get(spec["op"], 0.0)
        if not share or rate <= 0:
            return None
        blocks_per_s = rate * (1 << 20) * share \
            / ctx["config"]["erasure_block_bytes"]
        pat = re.compile(spec["modules"])
        mods = [m for m in tr["modules"] if pat.search(m["name"])]
        dev_s = sum(m["device_s"] for m in mods)
        if dev_s <= 0:
            raise LookupError(
                f"{blocks_per_s:.0f} blocks a second rode the device and no "
                f"traced program matches {spec['modules']!r} (the trace "
                f"holds {[m['name'] for m in tr['modules']]}): the "
                "metric's file names the programs of another kernel")
        least = work.least_seconds(spec["work"], ctx["config"],
                                   blocks_per_s, ctx["peaks"])
        ctx.setdefault("notes", {})[spec["work"] + "_roofline"] = {
            "blocks_per_s": blocks_per_s,
            "least_s_per_s": least["seconds"], "bound": least["bound"],
            "device_s_per_s": dev_s / tr["window_s"],
            "traced_s": tr["window_s"],
            "programs_run": {m["name"]: m["count"] for m in mods}}
        return least["seconds"] / (dev_s / tr["window_s"]) * 100.0
    raise ValueError(f"unknown quantity {spec['quantity']!r}")


def prometheus_gauge(ctx: dict, spec: dict):
    """A gauge as one scrape read it (`at`: "scrape_a" or "scrape_b",
    the window's two ends), summed over the series whose labels match.
    A program that does not export the series reports nothing."""
    scraped = ctx.get(spec["at"])
    if scraped is None or spec["series"] not in scraped:
        return None
    return series_sum(scraped, spec["series"], spec.get("labels"))


GENERIC = {"prometheus_delta": prometheus_delta, "device_trace": device_trace,
           "prometheus_gauge": prometheus_gauge}


def read_layer(ctx: dict, spec: dict):
    if "read" in spec:
        return spec["read"](ctx, spec)
    return GENERIC[spec["reader"]](ctx, spec)
