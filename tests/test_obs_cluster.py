"""Trace continuity + SLO views across the cluster (ISSUE 8 acceptance):
a 4-shard / 2-runner dedup job with one SIGKILL failover must still merge
into ONE trace (single job root, an attempt=2 re-lease span, zero
orphans) exportable as valid Chrome-trace JSON, and GET /cluster/slo must
serve queue-wait percentiles + per-runner throughput off log.jsonl."""
import json
import time

import pytest

from repro.api.cluster import ClusterQueue
from repro.api.slo import cluster_slo
from repro.core import obs
from repro.interface.cli import main as cli_main
from cluster_harness import (
    checkpoint_stages, make_sharded_recipe, reference_output, sigkill_runner,
    start_runner, stop_runner, wait_for, write_corpus,
)

pytestmark = pytest.mark.slow


def test_trace_continuity_across_sigkill_failover(tmp_path):
    """One sharded job, one killed runner, one merged trace."""
    src = write_corpus(str(tmp_path / "corpus.jsonl"), n=120)
    out = str(tmp_path / "out.jsonl")
    recipe = make_sharded_recipe(src, out, shards=4)
    recipe["process"].insert(1, {"name": "sleep_mapper", "delay": 0.05})
    ref = reference_output(recipe, str(tmp_path / "ref.jsonl"))

    q = ClusterQueue(str(tmp_path / "cluster"), lease_ttl=2.0)
    jid = q.submit(recipe)
    tr = q.read_spec(jid)["trace"]
    assert tr["trace_id"] and tr["root_span"], \
        "cluster submit must mint the trace ids up front"

    lead = start_runner(q.dir, "lead", lease_ttl=2.0)
    victim = None
    try:
        wait_for(lambda: q.current_lease(jid) is not None, 60,
                 message="parent claim")
        wait_for(lambda: len(q.shard_tasks(jid)) >= 4, 60,
                 message="shard tasks published")
        from repro.core.dedup.sharded import MAP_DELAY_ENV

        victim = start_runner(q.dir, "victim", lease_ttl=2.0,
                              extra_env={MAP_DELAY_ENV: "30"})

        def victim_map_task():
            for t in q.shard_tasks(jid):
                if "~s" in t:
                    lease = q.current_lease(t)
                    if lease is not None and lease.runner_id == "victim":
                        return t
            return None

        wait_for(lambda: victim_map_task() is not None, 60,
                 message="victim claims a map shard")
        vt = victim_map_task()
        wait_for(lambda: len(checkpoint_stages(q, vt)) >= 1, 60,
                 message="victim prefix checkpoint")
        time.sleep(0.2)
        sigkill_runner(victim)
        victim = None

        wait_for(lambda: q.state_of(jid) == "succeeded", 180,
                 message="sharded failover completion")
        with open(out, "rb") as f:
            assert f.read() == ref
        # the lead's parent-lease span flushes moments after the result
        # lands — wait for the spill, don't race it
        wait_for(lambda: any(
            s.get("kind") == "lease" and s.get("name") == f"lease:{jid}"
            for s in obs.read_spills(q.obs_dir())), 30,
            message="parent lease span spilled")
    finally:
        for p in (lead, victim):
            if p is not None:
                try:
                    stop_runner(p)
                except Exception:
                    pass

    spans = obs.merge_trace(q.obs_dir(), tr["trace_id"])
    tree = obs.span_tree(spans)

    # ONE job root — the parent's, span_id minted at submit — and no
    # orphans: the SIGKILLed attempt's unflushed spans are simply absent
    assert tree["roots"] == [tr["root_span"]]
    root = tree["by_id"][tr["root_span"]]
    assert root["kind"] == "job" and root["attrs"]["state"] == "succeeded"
    assert tree["orphans"] == [], \
        f"orphan spans after failover: {tree['orphans']}"

    kinds = {s["kind"] for s in spans}
    assert {"job", "shards", "lease", "run"} <= kinds and "op" not in kinds

    # the killed shard was re-leased: its accepted attempt is 2, and the
    # lease span from attempt 2 made it into the merged trace
    lease_attempts = [s["attrs"].get("attempt") for s in spans
                      if s["kind"] == "lease" and s["name"] == f"lease:{vt}"]
    assert 2 in lease_attempts, \
        f"re-lease span (attempt=2) missing for {vt}: {lease_attempts}"
    # every shard task's root span hangs off the parent job span
    task_roots = [s for s in spans
                  if s["kind"] == "job" and s["span_id"] != tr["root_span"]]
    assert task_roots and all(
        s["parent_id"] == tr["root_span"] for s in task_roots)
    # the shard-plan span recorded how the job was split
    plan = next(s for s in spans if s["kind"] == "shards")
    assert plan["attrs"]["n_shards"] == 4

    # CLI export: valid catapult JSON, loadable span tree
    trace_path = str(tmp_path / "TRACE_job.json")
    assert cli_main(["trace", jid, "--cluster_dir", q.dir,
                     "--out", trace_path]) == 0
    doc = json.load(open(trace_path))
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert len(xs) == len(spans)
    assert all({"name", "cat", "ts", "dur", "pid", "tid"} <= set(e)
               for e in xs)

    # SLO view off the same cluster dir: the failover shows up as a
    # failover count, both runners show up in throughput
    slo = cluster_slo(q.dir)
    assert slo["failovers"] >= 1
    assert slo["queue_wait"]["n"] >= 1
    assert slo["queue_wait"]["p95"] >= slo["queue_wait"]["p50"] >= 0.0
    assert "lead" in slo["throughput"]
    assert slo["throughput"]["lead"]["rows_per_second"] > 0
