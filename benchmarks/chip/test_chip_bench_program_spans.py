"""The server's spans against the device trace: the reduction on traces
built here, and the spans a real `XMCServer` writes on the CPU."""

import os
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402
import program_spans  # noqa: E402
import tracing  # noqa: E402
from tracing import Event, Trace  # noqa: E402

MS = 1e6
READERS = ("serve.idle_host_share", "serve.idle_hold_share",
           "serve.dispatch_host_p90_ms", "serve.complete_host_p90_ms")


def op(start_ms, dur_ms, device=0):
    return Event("fusion", start_ms * MS, dur_ms * MS, device, "XLA Ops")


def host(name, start_ms, end_ms):
    return Event(name, start_ms * MS, (end_ms - start_ms) * MS, None,
                 "host:python3")


def batch(form_ms, put_ms, launch_ms, end_ms):
    return [host("xmc.dispatch.form", form_ms, put_ms),
            host("xmc.dispatch.put", put_ms, launch_ms),
            host("xmc.dispatch.launch", launch_ms, end_ms)]


def completion(device_ms, fetch_ms, resolve_ms, end_ms):
    return [host("xmc.complete.device", device_ms, fetch_ms),
            host("xmc.complete.fetch", fetch_ms, resolve_ms),
            host("xmc.complete.resolve", resolve_ms, end_ms)]


def served_trace(program_spans_on=True):
    # Window 0-100 ms. Device 0 busy 10-20, 50-60 and 95-110 (cut at the
    # window's end): idle 0-10, 20-50, 60-95, 75 ms.
    events = [op(10, 10), op(50, 10), op(95, 15),
              host("bench.window", 0, 100), host("bench.submit", 40, 41)]
    program = [
        # A batch before the window, then the dispatcher's states.
        *batch(-10, -8, -7, -6),
        host("xmc.dispatch.idle", -6, 5),        # empty 0-5 of the idle
        host("xmc.dispatch.hold", 5, 10),        # hold 5-10
        *batch(10, 12, 13, 14),                  # 4 ms, device busy
        host("xmc.dispatch.handoff", 14, 15),
        host("xmc.dispatch.idle", 15, 30),       # empty 20-30
        host("xmc.dispatch.hold", 30, 45),       # hold 30-45
        *batch(45, 47, 48, 50),                  # 5 ms, host 45-50
        host("xmc.dispatch.handoff", 50, 52),
        host("xmc.dispatch.idle", 52, 70),       # empty 60-70
        host("xmc.dispatch.hold", 70, 80),       # hold 70-80
        *batch(80, 83, 84, 86),                  # 6 ms, host 80-86
        host("xmc.dispatch.handoff", 86, 96),    # back-pressure 86-95
        *batch(98, 99, 101, 102),                # ends past the window
        # Completion: fetch + resolve 3, 5 and 3 ms; one before the window.
        *completion(-5, -4, -3, -1),
        *completion(14, 20, 21, 23),
        *completion(50, 60, 61, 65),
        *completion(86, 96, 97, 99),
    ]
    return Trace(events=events + (program if program_spans_on else []),
                 window=(0.0, 100 * MS))


def read(name, trace):
    reader = harness.load_module(os.path.join(HERE, "layers", name + ".py"),
                                 "layer_" + name.replace(".", "_"))
    return reader.read({"trace": trace})


def test_idle_splits_by_the_dispatchers_state():
    parts = program_spans.idle_parts(served_trace())
    # Host: 45-50 and 80-95. Hold: 5-10, 30-45, 70-80. Empty: 0-5, 20-30,
    # 60-70. Spans before the window or past the device's ops are cut.
    assert parts["host"] == pytest.approx(0.20)
    assert parts["hold"] == pytest.approx(0.30)
    assert parts["empty"] == pytest.approx(0.25)
    assert sum(parts.values()) == pytest.approx(
        tracing.idle_share(served_trace()))
    assert read("serve.idle_host_share", served_trace()) == \
        pytest.approx(20.0)
    assert read("serve.idle_hold_share", served_trace()) == \
        pytest.approx(30.0)


def test_idle_is_read_on_device_zero():
    tr = served_trace()
    tr.events.append(op(0, 100, device=1))   # a second device, never idle
    assert program_spans.idle_parts(tr)["hold"] == pytest.approx(0.30)


@pytest.mark.parametrize("name,names,per", [
    ("serve.dispatch_host_p90_ms", program_spans.DISPATCH, [4.0, 5.0, 6.0]),
    ("serve.complete_host_p90_ms", program_spans.COMPLETE, [3.0, 5.0, 3.0]),
])
def test_per_batch_host_time_in_the_window(name, names, per):
    # The batches that start before the window or end after it are left
    # out; the rest sum their spans.
    assert program_spans.per_batch_ms(served_trace(), names) == \
        pytest.approx(per)
    assert read(name, served_trace()) == pytest.approx(
        float(np.percentile(per, 90)))


def test_a_batch_missing_a_span_is_not_counted():
    tr = Trace(events=[host("xmc.dispatch.form", 1, 2),      # never put
                       *batch(3, 4, 5, 7),
                       host("xmc.dispatch.put", 8, 9),       # no form
                       host("xmc.dispatch.launch", 9, 10)],
               window=(0.0, 100 * MS))
    assert program_spans.per_batch_ms(tr, program_spans.DISPATCH) == \
        pytest.approx([4.0])


@pytest.mark.parametrize("name", READERS)
def test_a_trace_without_program_spans_reads_nothing(name):
    # The parent program writes no xmc.* span: None, never 0.
    assert read(name, served_trace(program_spans_on=False)) is None
    assert read(name, None) is None


def test_server_writes_one_set_of_spans_per_batch(tmp_path):
    import jax.numpy as jnp

    from repro.core.pruning import prune, to_block_sparse
    from repro.serve import XMCEngine, XMCServer, make_backend

    L, D = 96, 128
    rng = np.random.default_rng(0)
    bsr = to_block_sparse(prune(jnp.asarray(
        rng.normal(size=(L, D)).astype(np.float32) * 0.1), 0.05), (128, 128))
    engine = XMCEngine(make_backend("dense", bsr, 3, n_labels=L),
                       buckets=(1, 2, 4), warmup=True, n_features=D)
    server = XMCServer(engine, max_batch_delay_ms=1.0)
    with tracing.capture(str(tmp_path)):
        with tracing.span("bench.window"):
            futures = []
            for i in range(12):
                futures.append(server.submit(
                    rng.normal(size=(1 + i % 3, D)).astype(np.float32)))
                time.sleep(0.002 * (i % 3))
            for f in futures:
                f.result(timeout=30)
            server.stop()
    batches = server.stats()["batches"]
    assert batches >= 2
    spans = program_spans.spans(tracing.load(str(tmp_path), "bench.window"))
    by = {n: [e for e in spans if e.name == n] for n in
          program_spans.DISPATCH + program_spans.COMPLETE}
    assert {n: len(v) for n, v in by.items()} == {n: batches for n in by}
    for form, launch in zip(by["xmc.dispatch.form"],
                            by["xmc.dispatch.launch"]):
        assert form.end_ns <= launch.start_ns
    assert any(e.name == program_spans.HOLD for e in spans)
