"""The trace reduction on hand-built events."""
import pytest

from benchlib import trace


def _meta(pid, name, threads):
    ev = [{"ph": "M", "name": "process_name", "pid": pid,
           "args": {"name": name}}]
    ev += [{"ph": "M", "name": "thread_name", "pid": pid, "tid": tid,
            "args": {"name": t}} for tid, t in threads.items()]
    return ev


def _x(pid, tid, name, ts, dur, **args):
    return {"ph": "X", "pid": pid, "tid": tid, "name": name, "ts": ts,
            "dur": dur, "args": args}


def events():
    ev = _meta(1, "/device:TPU:0", {1: "XLA Ops", 2: "XLA Modules"})
    ev += _meta(2, "/device:TPU:1", {1: "XLA Ops", 2: "XLA Modules"})
    ev += _meta(9, "/host:CPU", {5: "python3"})
    ev += [
        _x(1, 2, "jit_chunk_fn(7)", 0, 16),
        _x(1, 1, "fusion.1", 0, 10),
        _x(1, 1, "checkpoint.3", 5, 10,
           long_name="%checkpoint.3 = bf16[128,256]{1,0} custom-call("
                     "bf16[128,4096]{1,0} %a, bf16[4096,256]{1,0} %b), "
                     'custom_call_target="tpu_custom_call", '
                     "operand_layout_constraints={bf16[128,4096]{1,0}}"),
        _x(1, 1, "while.2", 0, 16, hlo_category="while"),
        _x(1, 2, "jit_admit_fn(8)", 29, 12),
        _x(1, 1, "fusion.22", 30, 10),
        _x(2, 1, "fusion.1", 0, 40),
        _x(9, 5, "serve.decode_chunk", 14, 18),
        _x(9, 5, "serve.prefill_admit", 41, 50),
        _x(9, 5, "PjitFunction(chunk_fn)", 0, 100),
    ]
    return ev


def test_union_busy_and_window():
    r = trace.reduce_events(events(), window_s=100e-6)
    assert r.devices == 2
    assert r.busy_per_device == pytest.approx([25e-6, 40e-6])
    assert r.busy_s == pytest.approx(32.5e-6)
    assert r.window_s == 100e-6
    assert [o.name for o in r.annotations] == ["serve.decode_chunk",
                                               "serve.prefill_admit"]


def test_modules_and_ops_inside_them():
    r = trace.reduce_events(events())
    assert r.module_seconds("jit_chunk_fn") == pytest.approx(16e-6)
    assert r.module_seconds("jit_admit_fn") == pytest.approx(12e-6)
    assert [o.name for o in r.ops_in("jit_chunk_fn")] == ["fusion.1",
                                                          "checkpoint.3"]
    assert [o.name for o in r.ops_in("jit_admit_fn")] == ["fusion.22"]


def test_gaps_named_by_the_host_annotation_over_them():
    r = trace.reduce_events(events())
    gaps = r.idle_gaps()
    # device 0 is busy on [0, 15] and [30, 40]; the trace spans [0, 100]
    assert gaps[0] == ["serve.prefill_admit", pytest.approx(60e-6)]
    assert gaps[1] == ["serve.decode_chunk", pytest.approx(15e-6)]
    assert len(gaps) == 2


def test_top_ops_fold_numbering_and_name_kernels():
    r = trace.reduce_events(events())
    top = dict((k, v) for k, v in r.top_ops())
    # the while loop's span covers its body's ops and is left out
    assert top == {"fusion": pytest.approx(20e-6),
                   "_gemm_kernel[(128,4096),(4096,256)]":
                   pytest.approx(10e-6)}
    assert trace.union_length([(0, 2), (1, 3), (5, 6)]) == 4


def test_no_device_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce_events(_meta(9, "/host:CPU", {5: "python3"}))


def test_recorded_chip_trace():
    """One decode chunk of chatglm3-6b at 8 rows, recorded on a TPU v5e
    (``--trace 1``), kept to its XLA ops, modules and serve.* spans."""
    import gzip
    import json
    import pathlib
    from benchlib import kernels
    from benchlib.peaks import peaks_for
    path = pathlib.Path(__file__).parent / "data" / \
        "decode_chunk.trace.json.gz"
    with gzip.open(path, "rt") as f:
        r = trace.reduce_events(json.load(f)["traceEvents"])
    assert r.devices == 1
    # the chunk, and the start of the next one 7 ms after it
    assert r.module_seconds("jit_chunk_fn") == pytest.approx(2 * 0.716096,
                                                             rel=1e-4)
    ops = r.ops_in("jit_chunk_fn")
    kinds = {kernels.kernel_kind(o) for o in ops}
    assert kinds == {None, "_gemm_kernel"}          # no flash in decode
    share = kernels.roofline_share(ops, "_gemm_kernel", kernels.gemm_least,
                                   peaks_for("TPU v5 lite"))
    assert 15.0 < share < 40.0
    top = r.top_ops(3)
    assert top[0][0] == "_gemm_kernel[(128,4096),(4096,13696)]"
    # ops cover the 0.716 s chunk but for its 8 host-side gaps, and 40 ms
    # around it
    assert 0.70 < r.busy_per_device[0] < 0.76
    assert "serve.decode_chunk" in {a.name for a in r.annotations}
