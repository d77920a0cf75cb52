"""``attention_core_fwd_runs_per_layer`` on a hand-encoded trace: the
reader counts the core-forward kernels of whole step executions alone."""

from types import SimpleNamespace

from benchmark.layer_metrics import attention_core_fwd_runs_per_layer as m
from benchmark.selftest.test_program_spans import CHIP, _entry, _f


def _plane(name, metadata, lines):
    """``lines``: {line name: [(metadata id, start ns, duration ns)]}."""
    return _f(1, _f(2, name) + b"".join(
        _f(3, _f(2, line) + _f(3, 0) + b"".join(
            _f(4, _f(1, i) + _f(2, off * 1000) + _f(3, dur * 1000))
            for i, off, dur in events))
        for line, events in lines.items()) + b"".join(
        _f(4, _entry(k, _f(1, k) + _f(2, text)))
        for k, text in metadata.items()))


def _trace(tmp_path, kernels):
    """A slice [1000, 17000) ns; step executions at 500 (cut by the
    slice's start), 4500, 8500 (whole), 12500 and 16600 (whole; cut by
    its end), 4,000 ns each; ``kernels``: [(metadata id, offset in a step)] run in
    every step."""
    names = {1: "jit_train_step(7)", 2: "jit_eval_step(9)",
             3: "%flash_fwd.2 = (bf16[28,8192,128]) custom-call(...)",
             4: "%sparse_fwd.11 = (bf16[4,8,16384,128]) custom-call(...)",
             5: "%flash_bwd_dq.1 = bf16[28,8192,128] custom-call(...)",
             6: "%fusion.3 = f32[8] fusion(...)"}
    starts = (500, 4500, 8500, 12500, 16600)
    trace = _plane("/host:CPU", {1: "bench_slice"},
                   {"python3": [(1, 1000, 16000)]})
    trace += _plane("/device:TPU:0", names, {
        # ... and an entry under the step's name that ran no step
        "XLA Modules": [(1, s, 4000) for s in starts] + [(2, 16540, 50),
                                                         (1, 8490, 5)],
        "XLA Ops": [(k, s + off, 100) for s in starts for k, off in kernels],
    })
    # a second device's plane is not read
    trace += _plane("/device:TPU:1", names, {
        "XLA Modules": [(1, 4500, 4000)],
        "XLA Ops": [(3, 4600 + 200 * i, 100) for i in range(9)]})
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(trace)
    return SimpleNamespace(trace_file=lambda: str(path))


def _record(tracer, layout=(0, 1)):
    return {"tracer": tracer,
            "model_cfg": SimpleNamespace(layer_layout=layout)}


def test_two_runs_a_layer_where_the_replay_runs_the_core_again(tmp_path):
    kernels = [(3, 100), (3, 300), (6, 500), (3, 2000), (5, 2200),
               (3, 3000), (5, 3200)]
    assert m.read(_record(_trace(tmp_path, kernels))) == 2.0


def test_one_run_a_layer_where_the_forward_pass_kept_its_output(tmp_path):
    kernels = [(4, 100), (4, 300), (6, 500), (5, 2200), (5, 3200)]
    assert m.read(_record(_trace(tmp_path, kernels))) == 1.0
    assert m.read(_record(_trace(tmp_path, kernels), (2, 2, 2, 2))) == 0.5


def test_a_step_without_the_kernels_or_a_run_without_a_trace_reads_nothing(
        tmp_path):
    assert m.read(_record(_trace(tmp_path, [(6, 500), (5, 700)]))) is None
    assert m.read(_record(None)) is None
    assert m.read(_record(_trace(tmp_path, [(3, 100)]), ())) is None
    assert m.read({"tracer": _trace(tmp_path, [(3, 100)])}) is None
    # a width-32 trainer's five steps, recorded on the chip
    assert m.read(_record(SimpleNamespace(trace_file=lambda: CHIP))) is None
