"""Dashboard parity-lite (VERDICT r3 next #8): the four views — job DAG
SVG, per-subtask backpressure bars, checkpoint drill-down table, flame
graph SVG — render server-side from REST data and are asserted as DOM here
(SVG parsed with ElementTree, fragments with html.parser; no browser in
this image).  Reference: ``flink-runtime-web/web-dashboard``."""

import threading
import urllib.request
import xml.etree.ElementTree as ET
from html.parser import HTMLParser

import numpy as np
import pytest

from flink_tpu.cluster.minicluster import MiniCluster
from flink_tpu.datastream.api import StreamExecutionEnvironment
from flink_tpu.rest.server import JobRegistry, RestServer
from flink_tpu.runtime.checkpoint.storage import InMemoryCheckpointStorage

SVG = "{http://www.w3.org/2000/svg}"


def _get_text(url):
    with urllib.request.urlopen(url, timeout=15) as r:
        return r.read().decode(), r.headers.get_content_type()


@pytest.fixture
def job(tmp_path):
    registry = JobRegistry()
    server = RestServer(registry).start()
    env = StreamExecutionEnvironment()
    env.set_parallelism(2)
    n = 400_000
    keys = np.arange(n) % 97
    (env.from_collection(columns={"k": keys, "v": np.ones(n)},
                         batch_size=256)
     .key_by("k").sum("v").collect())
    plan = env.get_stream_graph("dash-job").to_plan()
    mc = MiniCluster(checkpoint_storage=InMemoryCheckpointStorage(),
                     checkpoint_interval_ms=10)
    job_id = registry.register("dash-job", mc)
    th = threading.Thread(target=lambda: mc.execute(plan, timeout_s=120))
    th.start()
    base = f"{server.url}/jobs/{job_id}"
    # wait until every vertex deployed (the views read live task state)
    import json
    import time
    deadline = time.time() + 30
    while time.time() < deadline:
        with urllib.request.urlopen(base, timeout=10) as r:
            st = json.loads(r.read())
        if len(st["vertices"]) >= len(plan.vertices):
            break
        time.sleep(0.05)
    try:
        yield base, plan
    finally:
        th.join(timeout=120)
        server.stop()


class _Frag(HTMLParser):
    def __init__(self):
        super().__init__()
        self.tags = []

    def handle_starttag(self, tag, attrs):
        self.tags.append((tag, dict(attrs)))


def test_dag_svg_renders_plan(job):
    base, plan = job
    body, ctype = _get_text(base + "/plan.svg")
    assert ctype == "image/svg+xml"
    root = ET.fromstring(body)
    assert root.tag == f"{SVG}svg"
    groups = root.findall(f"{SVG}g")
    vertex_groups = [g for g in groups
                     if g.get("class") == "dag-vertex"]
    assert len(vertex_groups) == len(plan.vertices)
    # every vertex renders its name and parallelism
    texts = [t.text for g in vertex_groups for t in g.findall(f"{SVG}text")]
    for v in plan.vertices:
        assert any(v.name in (t or "") for t in texts), v.name
    # edges drawn with arrowheads
    edges = [p for p in root.findall(f"{SVG}path")
             if p.get("class") == "dag-edge"]
    want_edges = sum(len(v.out_edges) for v in plan.vertices)
    assert len(edges) == want_edges
    # partitioning labels present (HASH edge from key_by)
    labels = [t.text for t in root.findall(f"{SVG}text")
              if t.get("class") == "dag-edge-label"]
    assert any("HASH" in (l or "").upper() for l in labels), labels


def test_backpressure_fragment_has_per_subtask_bars(job):
    base, plan = job
    body, ctype = _get_text(base + "/backpressure.html")
    assert ctype == "text/html"
    p = _Frag()
    p.feed(body)
    subtasks = [a for t, a in p.tags
                if a.get("class") == "bp-subtask"]
    # parallelism 2: at least one vertex shows 2 subtask rows
    by = {}
    for t, a in p.tags:
        if a.get("class") == "bp-vertex":
            by[a.get("data-vertex-id")] = 0
    assert len(by) == len(plan.vertices)
    assert len(subtasks) >= 2
    bars = [a for t, a in p.tags if a.get("class") in
            ("bp-busy", "bp-backpressured", "bp-idle")]
    assert len(bars) == 3 * len(subtasks)
    for a in bars:
        assert "width:" in a.get("style", "")


def test_checkpoint_drilldown_table(job):
    base, _plan = job
    import json
    import time
    import urllib.request as _u
    deadline = time.time() + 30
    while time.time() < deadline:
        with _u.urlopen(base + "/checkpoints", timeout=10) as r:
            ck = json.loads(r.read())
        if ck["count"] >= 1:
            break
        time.sleep(0.1)
    assert ck["count"] >= 1, "no checkpoint completed in time"
    body, ctype = _get_text(base + "/checkpoints.html")
    assert ctype == "text/html"
    p = _Frag()
    p.feed(body)
    rows = [a for t, a in p.tags if a.get("class") == "ckpt-row"]
    assert rows and all("data-checkpoint-id" in a for a in rows)
    assert any(t == "table" for t, _a in p.tags)
    assert body.count("<th>") == 5          # id/state/duration/size/kind
    # the state-size column renders real sizes, not the placeholder
    assert "state_size_bytes" not in body
    assert any(c.isdigit() for c in body.split("</td><td>")[3])


def test_flamegraph_svg_truncated_labels_stay_well_formed():
    """A label cut to its frame's width must be cut before it is escaped:
    a cut through ``&lt;`` is not XML."""
    from flink_tpu.rest.views import flamegraph_svg

    tree = {"name": "root", "value": 10, "children": [
        {"name": "<lambda> (a&b.py:1) " + "x" * 60, "value": 1,
         "children": []}]}
    for width in range(100, 1200, 7):
        ET.fromstring(flamegraph_svg(tree, width=width))


def test_flamegraph_svg_renders_samples(job):
    base, _plan = job
    body, ctype = _get_text(base + "/flamegraph.svg")
    assert ctype == "image/svg+xml"
    root = ET.fromstring(body)
    frames = [g for g in root.findall(f"{SVG}g")
              if g.get("class") == "flame-frame"]
    assert frames, "no stack frames sampled"
    # root frame spans the full width; every frame carries a tooltip title
    rects = [g.find(f"{SVG}rect") for g in frames]
    widths = [float(r.get("width")) for r in rects]
    assert max(widths) == pytest.approx(1000.0, abs=1.0)
    titles = [r.find(f"{SVG}title") for r in rects]
    assert all(t is not None and "samples" in t.text for t in titles)
    # depth attribute increases monotonically from the root
    depths = sorted(int(g.get("data-depth")) for g in frames)
    assert depths[0] == 0 and depths[-1] >= 1


def test_plan_json_topology(job):
    base, plan = job
    import json
    with urllib.request.urlopen(base + "/plan", timeout=10) as r:
        view = json.loads(r.read())
    assert {v["id"] for v in view["vertices"]} == {v.id
                                                   for v in plan.vertices}
    assert all({"source", "target", "partitioning"} <= set(e)
               for e in view["edges"])
