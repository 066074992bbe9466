"""Spans and counters (core/tracing.py): off, on, across threads, and
along one served batch from the generator to the executor's close."""

import subprocess
import sys
import threading
import tracemalloc
from collections import deque
from pathlib import Path

import pytest

from repro.core import tracing

SRC = Path(__file__).resolve().parents[1] / "src"


@pytest.fixture()
def on():
    tracing.drain()
    tracing.enable()
    yield
    tracing.disable()
    tracing.drain()


def test_off_returns_one_shared_context_and_records_nothing():
    tracing.drain()
    assert tracing.span("colony.a") is tracing.span("colony.b", x=1)
    with tracing.span("colony.a") as sp:
        sp.set(processid="p")
    tracing.record("colony.c", 0.0, 1.0)
    tracemalloc.start()
    try:
        for _ in range(3):
            with tracing.span("colony.a"):
                pass
        before = tracemalloc.take_snapshot()
        for _ in range(1000):
            with tracing.span("colony.a"):
                pass
        after = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    grown = [d for d in after.compare_to(before, "filename")
             if d.traceback[0].filename == tracing.__file__]
    assert sum(d.size_diff for d in grown) == 0
    assert tracing.drain()["spans"] == []


def test_nesting_parents_and_processid(on):
    with tracing.span("colony.outer", k=1) as outer:
        with tracing.span("colony.before"):
            pass
        outer.set(processid="p1", n=2)
        with tracing.span("colony.inner"):
            with tracing.span("colony.leaf", processid="p2"):
                pass
    spans = {s["name"]: s for s in tracing.drain()["spans"]}
    assert list(spans) == ["colony.before", "colony.leaf", "colony.inner", "colony.outer"]
    o = spans["colony.outer"]
    assert o["parent"] is None and o["attrs"] == {"k": 1, "n": 2} and o["processid"] == "p1"
    assert spans["colony.before"]["parent"] == o["id"] and spans["colony.before"]["processid"] is None
    assert spans["colony.inner"]["parent"] == o["id"] and spans["colony.inner"]["processid"] == "p1"
    assert spans["colony.leaf"]["parent"] == spans["colony.inner"]["id"]
    assert spans["colony.leaf"]["processid"] == "p2"
    assert o["start"] <= spans["colony.leaf"]["start"] <= spans["colony.leaf"]["end"] <= o["end"]


def test_threads_keep_their_own_parents(on):
    gate = threading.Barrier(2, timeout=30)

    def work(name):
        with tracing.span(f"colony.{name}"):
            gate.wait()
            with tracing.span(f"colony.{name}.child"):
                gate.wait()

    threads = [threading.Thread(target=work, args=(n,)) for n in ("a", "b")]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
        assert not t.is_alive()
    spans = {s["name"]: s for s in tracing.drain()["spans"]}
    for n in ("a", "b"):
        assert spans[f"colony.{n}.child"]["parent"] == spans[f"colony.{n}"]["id"]
        assert spans[f"colony.{n}.child"]["thread"] == spans[f"colony.{n}"]["thread"]
    assert spans["colony.a"]["thread"] != spans["colony.b"]["thread"]


def test_no_update_is_lost_across_threads(on):
    workers, each = 16, 500
    mine = tracing.Counters("test.stress.")
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(each):
                with tracing.span("colony.stress"):
                    mine.count("n", 1, 0.5)

        threads = [threading.Thread(target=work) for _ in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(switch)
    assert mine.get("n") == (workers * each, 0.5 * workers * each)
    spans = [s for s in tracing.drain()["spans"] if s["name"] == "colony.stress"]
    assert len(spans) == workers * each and len({s["id"] for s in spans}) == workers * each


def test_the_buffer_is_bounded(on, monkeypatch):
    monkeypatch.setattr(tracing, "BUFFER_SPANS", 3)
    monkeypatch.setattr(tracing, "_buffer", deque(maxlen=3))
    for i in range(5):
        with tracing.span("colony.s", i=i):
            pass
    out = tracing.drain()
    assert [s["attrs"]["i"] for s in out["spans"]] == [2, 3, 4]
    assert out["dropped"] == 2
    assert tracing.drain() == {"spans": [], "dropped": 0, "counters": tracing.counters()}


def test_counters_count_whether_on_or_not():
    before = tracing.counters().get("test.thing", {"n": 0, "seconds": 0.0})
    tracing.count("test.thing")
    tracing.count("test.thing", 2, 0.5)
    mine = tracing.Counters("test.own.")
    mine.count("x", 4, 1.0)
    now = tracing.counters()
    assert now["test.thing"]["n"] == before["n"] + 3
    assert now["test.thing"]["seconds"] == pytest.approx(before["seconds"] + 0.5)
    assert now["test.own.x"] == {"n": 4, "seconds": 1.0} and mine.get("x") == (4, 1.0)
    del mine
    assert "test.own.x" not in tracing.counters()


def test_an_installed_profiler_annotates_and_switches_recording_on(monkeypatch):
    opened, capturing = [], [False]

    class Annotation:
        def __init__(self, name):
            opened.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return None

    monkeypatch.setattr(tracing, "_annotate", None)
    monkeypatch.setattr(tracing, "_capturing", None)
    tracing.drain()
    tracing.install(Annotation, lambda: capturing[0])
    with tracing.span("colony.quiet"):
        pass
    capturing[0] = True
    with tracing.span("colony.loud"):
        pass
    tracing.install(None)
    assert opened == ["colony.loud"]
    assert [s["name"] for s in tracing.drain()["spans"]] == ["colony.loud"]


def test_the_load_generators_imports_leave_jax_out():
    code = ("import sys\n"
            "import repro.core.tracing, repro.core.client, repro.core.fs, repro.serve.batcher\n"
            "assert 'jax' not in sys.modules, [m for m in sys.modules if 'jax' in m]\n")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120,
                   env={"PYTHONPATH": str(SRC), "PATH": "/usr/bin:/bin"})


def test_one_batch_is_one_tree_under_its_processid(colony, on):
    """generator -> assign -> executor -> engine -> uploads -> close."""
    from repro.core.fs import CFSClient, MemoryStorage
    from repro.runtime.jax_executor import ServeExecutor
    from repro.serve.batcher import InferenceClient

    client, srv, prv = colony["client"], colony["server"], colony["colony_prv"]
    cfs = CFSClient(client, MemoryStorage(), prv)
    srv.start_background(failsafe_interval=0.05)
    ex = ServeExecutor(client, "dev", "serve-t", "tpu-serve", cfs.storage, colony_prvkey=prv,
                       arch="stablelm-3b", max_len=32)
    wf = {"colonyname": "dev", "functionspecs": [
        {"nodename": "batch", "funcname": "generate_batch",
         "conditions": {"executortype": "tpu-serve", "dependencies": []}}]}
    g = client.add_generator({"colonyname": "dev", "name": "g", "queuesize": 2, "timeout": 5.0,
                              "workflow": wf}, prv)
    ex.start(poll_timeout=0.2)
    infc = InferenceClient(client, cfs, "dev", g["generatorid"], prv)
    rids = [infc.submit([1, 2, 3, 4 + i], max_new_tokens=3) for i in range(2)]
    outs = [infc.wait(r, timeout=60) for r in rids]
    ex.stop()
    assert all(len(o) == 3 for o in outs)
    stats = client.stats("dev", prv)
    spans = tracing.drain()["spans"]

    fire = next(s for s in spans if s["name"] == "colony.generator.fire")
    pid = fire["processid"]
    assert fire["attrs"]["reason"] == "full" and fire["attrs"]["packs"] == 2
    assert 0 <= fire["attrs"]["mean_hold_s"] <= fire["attrs"]["oldest_hold_s"]
    mine = [s for s in spans if s["processid"] == pid]
    by_id = {s["id"]: s for s in spans}
    proc = next(s for s in mine if s["name"] == "colony.executor.process")
    assert proc["attrs"]["funcname"] == "generate_batch"
    assert any(s["name"] == "colony.executor.assign" for s in mine)

    def under(s):
        chain = []
        while s["parent"] is not None:
            s = by_id[s["parent"]]
            chain.append(s["name"])
        return chain

    children = {s["name"] for s in mine if s["parent"] == proc["id"]}
    assert {"colony.batch.build", "colony.engine.generate", "colony.batch.upload",
            "colony.executor.close"} <= children
    gen = next(s for s in mine if s["name"] == "colony.engine.generate")
    assert gen["attrs"] == {"batch": 2, "seq": 4, "new": 3}
    assert [s["name"] for s in mine if s["parent"] == gen["id"]] == [
        "colony.engine.prefill", "colony.engine.decode", "colony.engine.collect"]
    upload = next(s for s in mine if s["name"] == "colony.batch.upload")
    assert upload["attrs"]["files"] == 2
    signed = [s for s in mine if s["name"] == "colony.rpc.sign"
              and "colony.batch.upload" in under(s)]
    assert [s["attrs"]["payloadtype"] for s in signed] == ["addfile", "addfile"]
    verified = [s for s in mine if s["name"] == "colony.rpc.verify"]
    assert all("colony.server.handle" in under(s) for s in verified)
    assert {s["thread"] for s in mine if s["name"] != "colony.generator.fire"} == {proc["thread"]}
    waits = [s for s in spans if s["name"] == "colony.broker.assign_wait"]
    assert waits and {"colony.executor.assign", "colony.server.handle"} <= set(under(waits[0]))

    trace = stats["trace"]
    assert trace["generator.fires.full"]["n"] >= 1
    assert trace["rpc.verify.pack"]["n"] >= 2 and trace["rpc.verify.pack"]["seconds"] > 0
    assert trace["rpc.sign.addfile"]["n"] >= 2
    assert trace["engine.batches"]["n"] >= 1 and ex.engine.stats["batches"] == 1
    assert any(k.startswith("jax.compiles.") for k in trace)
