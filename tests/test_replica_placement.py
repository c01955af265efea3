"""Replica placement (`MultiNodeHTAP(replica_devices=...)`) on four forced
CPU devices: each replica's uploads, view tiles and kernel operands live
on its own device, its serves and ships copy nothing between devices, and
its answers equal those of an unplaced cluster fed the same WAL and of the
chain-store oracle.

The device count has to be set before JAX starts, so the cluster runs in a
subprocess, once for the module; each test reads one part of what it
reports."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

SCRIPT = r'''
import json, random
import jax
from repro.mvcc import MultiNodeHTAP
from repro.mvcc.workload import Scale, load_initial, oltp_transaction
from repro.obs import REGISTRY, TRACER
from repro.tensorstore.version_store import (AggOp, AggPlan, GroupByPlan,
                                             MultiAggPlan, ScanPlan,
                                             apply_plan, plan_keys)

sc = Scale(warehouses=2, districts=4, customers=10, items=40,
           order_capacity=8)
devs = jax.local_devices()
out = {"errors": {}}

def facade(replica_devices):
    return MultiNodeHTAP("ssi+rss", paged_olap=True, check_scans=True,
                         n_replicas=4, route_policy="round_robin",
                         reserve_keys=sc.key_families(),
                         materialize=sc.materialized_plans(),
                         replica_devices=replica_devices)

for name, bad in (("length", [0, 1, 2]), ("range", [0, 1, 2, 4]),
                  ("negative", [0, 1, 2, -1])):
    try:
        facade(bad)
        out["errors"][name] = None
    except ValueError as exc:
        out["errors"][name] = str(exc)

TRACER.set_enabled(True)
placed, unplaced = facade([0, 1, 2, 3]), facade(None)
stock = tuple(sc.all_stock_keys())
orders = tuple(tuple(sc.order_range_keys(w, d)) for w in range(2)
               for d in range(4))
plans = {
    "scan": ScanPlan(tuple(sc.all_district_keys())),
    "scalar": AggPlan(stock, AggOp("count_below", "int", 30)),
    "multi": MultiAggPlan(stock, (AggOp("sum", "int"), AggOp("max", "int"),
                                  AggOp("count_above", "int", 60))),
    "group_host": GroupByPlan(orders[:2], (AggOp("sum", "total"),)),
    "group_flat": GroupByPlan(orders, (AggOp("max", "total"),
                                       AggOp("count", "total"))),
    "group_chunked": GroupByPlan(tuple(stock[i:i + 2]
                                       for i in range(0, 80, 2)),
                                 (AggOp("sum", "int"),)),
}
for i, p in enumerate(sc.materialized_plans()):
    plans[f"view{i}"] = p

def drive(htap, seed):
    """The same transactions, one at a time, so both WALs are equal."""
    rng = random.Random(seed)
    for _ in range(12):
        gen, _name = oltp_transaction(rng, sc)
        t = htap.primary.begin()
        val = None
        try:
            while True:
                step = gen.send(val)
                val = None
                if step[0] == "r":
                    val = htap.primary.read(t, step[1])
                elif step[0] == "w":
                    htap.primary.write(t, step[1], step[2])
        except StopIteration:
            pass
        htap.primary.commit(t)

served = {i: set() for i in range(4)}
answers = []          # (replica, plan, == unplaced answer, == oracle)
serves = 0
for h in (placed, unplaced):
    load_initial(h.primary, sc)
with jax.transfer_guard_device_to_device("disallow"):
    for rnd in range(3):
        for h in (placed, unplaced):
            drive(h, 1000 + rnd)
            h.ship_log()
        for _ in range(4):
            hp, hu = placed.olap_snapshot(), unplaced.olap_snapshot()
            assert hp[1] == hu[1]
            for name, plan in plans.items():
                a, b = placed.olap_execute(hp, plan), \
                    unplaced.olap_execute(hu, plan)
                oracle = apply_plan([placed.olap_read(hp, k)
                                     for k in plan_keys(plan)], plan)
                answers.append((hp[1], name, a == b, a == oracle))
                served[hp[1]].add(name)
                serves += 1
            pair = [(hp, plans["scalar"]), (hp, plans["multi"])]
            a = placed.olap_execute_batch(pair)
            b = unplaced.olap_execute_batch([(hu, p) for _, p in pair])
            answers.append((hp[1], "batch", a == b, True))
            served[hp[1]].add("batch")
            serves += 1
            if rnd == 2:
                rep = placed.cluster.replicas[hp[1]]
                out.setdefault("stores", {})[hp[1]] = sorted(
                    {str(d) for store, *_ in rep.mirror._store_cache.values()
                     for arr in store.values() for d in arr.devices()})
            placed.olap_release(hp)
            unplaced.olap_release(hu)
        for h in (placed, unplaced):
            h.gc_versions()

out["answers"] = answers
out["served"] = {i: sorted(s) for i, s in served.items()}
out["plans"] = sorted(list(plans) + ["batch"])
out["devices"] = [str(d) for d in devs[:4]]
out["tiles"] = {i: sorted({str(d) for v in rep.mirror.views.values()
                           for d in v.acc.devices()})
                for i, rep in enumerate(placed.cluster.replicas)}
out["unplaced_tiles"] = sorted({str(d) for rep in unplaced.cluster.replicas
                                for v in rep.mirror.views.values()
                                for d in v.acc.devices()})
out["view_hits"] = [rep.mirror.exec_stats["view_hits"]
                    for rep in placed.cluster.replicas]
out["serves"] = serves
out["served_by"] = [placed.cluster.stats["served"][i] for i in range(4)]
out["h2d_chips"] = sorted(dict(m.labels).get("chip", "-")
                          for m in REGISTRY.series("mirror_h2d_bytes"))

def labels(spans, name):
    for s in spans:
        if s.name == name:
            yield s.labels
        yield from labels(s.children, name)

out["serve_chips"] = sorted({str(lb.get("chip", "-"))
                             for lb in labels(TRACER.traces, "olap_serve")})
out["ship_chips"] = sorted({str(lb.get("chip", "-"))
                            for lb in labels(TRACER.traces, "ship_replay")})
print(json.dumps(out))
'''


@pytest.fixture(scope="module")
def report():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_placed_answers_equal_unplaced_and_oracle(report):
    answers = report["answers"]
    assert answers
    bad = [a for a in answers if not (a[2] and a[3])]
    assert not bad, bad[:5]


def test_every_plan_shape_served_on_every_replica(report):
    for i in range(4):
        assert report["served"][str(i)] == report["plans"], i
    assert all(n > 0 for n in report["view_hits"]), report["view_hits"]


def test_view_tiles_and_cached_stores_on_the_replicas_device(report):
    devs = report["devices"]
    for i in range(4):
        assert report["tiles"][str(i)] == [devs[i]]
        assert report["stores"][str(i)] == [devs[i]]
    # unplaced replicas keep to JAX's default device
    assert report["unplaced_tiles"] == [devs[0]]


@pytest.mark.parametrize("case", ["length", "range", "negative"])
def test_a_wrong_replica_devices_list_is_a_value_error(report, case):
    assert report["errors"][case], case


def test_round_robin_deals_the_snapshots_evenly(report):
    # 3 rounds of 4 snapshots over 4 replicas; each snapshot carried
    # every plan and one batch
    assert report["served_by"] == [3, 3, 3, 3]
    assert sum(report["served_by"]) * len(report["plans"]) == \
        report["serves"]


def test_chip_labels_on_placed_replicas_only(report):
    chips = [f"cpu:{i}" for i in range(4)]
    # four placed mirrors with a chip label, four unplaced ones without
    assert report["h2d_chips"] == ["-"] * 4 + chips
    assert report["serve_chips"] == ["-"] + chips
    assert report["ship_chips"] == ["-"] + chips
