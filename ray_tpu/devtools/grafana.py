"""Grafana dashboard generator — one panel per catalog metric.

``python -m ray_tpu.devtools.grafana [-o PATH]`` writes the dashboard
(to stdout without `-o`) from `ray_tpu.util.metrics_catalog` (the
machine-readable metric registry). Nothing generated is committed: an
operator generates the file for the checkout they run. Deterministic
output: same catalog, byte-identical JSON; the gate in
tests/test_observability4.py holds one panel a catalogued metric.

Panel expression by type (the cluster /metrics page is the datasource,
every series tagged node=/proc= by the aggregation layer):

- counter   -> ``rate(name[5m])``, legended by node
- gauge     -> ``name``
- histogram -> p50/p99 via ``histogram_quantile`` over bucket rates

Rows group panels by metric prefix (train/serve_llm/object_store/...).
"""

from __future__ import annotations

import json
import os
import sys

from ray_tpu.util.metrics_catalog import CATALOG

DATASOURCE = {"type": "prometheus", "uid": "${DS_PROMETHEUS}"}

_GROUPS = (
    ("train", "Train"),
    ("collective", "Collectives"),
    ("object_store", "Object store"),
    ("serve_llm", "serve.llm engine"),
    ("serve_slo", "Serving SLO attribution"),
    ("serve", "Serve proxy"),
    ("rl", "RL flywheel"),
    ("profile", "Profiler plane"),
    ("log", "Logs"),
    ("spans", "Span plane"),
    ("watchtower", "Alerts"),
)


def _group_of(name: str) -> str:
    for prefix, title in _GROUPS:
        if name == prefix or name.startswith(prefix + "_"):
            return title
    return "Other"


def _targets(metric: dict) -> list[dict]:
    name, mtype = metric["name"], metric["type"]
    if mtype == "counter":
        return [{"expr": f"rate({name}[5m])",
                 "legendFormat": "{{node}}/{{proc}}", "refId": "A"}]
    if mtype == "gauge":
        return [{"expr": name,
                 "legendFormat": "{{node}}/{{proc}}", "refId": "A"}]
    return [
        {"expr": ("histogram_quantile(0.5, sum by (le) "
                  f"(rate({name}_bucket[5m])))"),
         "legendFormat": "p50", "refId": "A"},
        {"expr": ("histogram_quantile(0.99, sum by (le) "
                  f"(rate({name}_bucket[5m])))"),
         "legendFormat": "p99", "refId": "B"},
    ]


def build_dashboard() -> dict:
    """The dashboard dict, grouped into collapsible rows by prefix.
    Grid: 2 panels per row of 12x8 units; ids assigned in catalog
    order (stable across regenerations by construction)."""
    panels: list[dict] = []
    panel_id = 1
    y = 0
    current_group = None
    x = 0
    for m in CATALOG:
        group = _group_of(m["name"])
        if group != current_group:
            if current_group is not None and x > 0:
                y += 8
            panels.append({
                "id": panel_id, "type": "row", "title": group,
                "collapsed": False,
                "gridPos": {"h": 1, "w": 24, "x": 0, "y": y},
            })
            panel_id += 1
            y += 1
            x = 0
            current_group = group
        panels.append({
            "id": panel_id,
            "type": "timeseries",
            "title": m["name"],
            "description": f"{m['what']} ({m['where']})",
            "datasource": DATASOURCE,
            "targets": _targets(m),
            "fieldConfig": {"defaults": {"custom": {"fillOpacity": 8}},
                            "overrides": []},
            "gridPos": {"h": 8, "w": 12, "x": x, "y": y},
        })
        panel_id += 1
        if x == 0:
            x = 12
        else:
            x = 0
            y += 8
    return {
        "__inputs": [{"name": "DS_PROMETHEUS", "label": "Prometheus",
                      "type": "datasource",
                      "pluginId": "prometheus"}],
        "title": "ray_tpu cluster",
        "uid": "ray-tpu-cluster",
        "tags": ["ray_tpu", "generated"],
        "timezone": "browser",
        "schemaVersion": 39,
        "refresh": "10s",
        "time": {"from": "now-30m", "to": "now"},
        "templating": {"list": [{
            "name": "node", "type": "query",
            "datasource": DATASOURCE,
            "query": "label_values(node)", "refresh": 2,
            "includeAll": True, "multi": True,
        }]},
        "panels": panels,
    }


def dashboard_json() -> str:
    return json.dumps(build_dashboard(), indent=1, sort_keys=True) + "\n"


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(prog="python -m ray_tpu.devtools.grafana")
    ap.add_argument("-o", "--output", default=None,
                    help="file to write (default: stdout)")
    args = ap.parse_args(argv)
    text = dashboard_json()
    if args.output is None:
        sys.stdout.write(text)
        return 0
    os.makedirs(os.path.dirname(args.output) or ".", exist_ok=True)
    with open(args.output, "w") as f:
        f.write(text)
    print(f"wrote {args.output} ({len(CATALOG)} metrics)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
