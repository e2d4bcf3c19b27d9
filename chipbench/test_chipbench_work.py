"""Work counts and the per-layer arithmetic, against hand counts."""
from __future__ import annotations

import pytest

from chipbench import spec
from chipbench.device_trace import Summary
from chipbench.common import RunData
from chipbench.families import dense


def _dims(config: str) -> dense.Dims:
    conf = spec._load_json(spec.HERE / "configs" / f"{config}.json")
    return spec.family(conf).dims(conf)


def test_smollm_flops():
    dm = _dims("smollm-360m")
    # 32 x (960*960*2 + 2*960*320 + 3*960*2560) + 49152*960 (tied head)
    assert dense.matmul_params(dm) == 32 * 9_830_400 + 47_185_920
    per_step = dense.train_flops_per_token(dm, 1024) * 8 * 1024
    # 6 * 361.76 M * 8192 + 12 * 32 * 15 * 64 * 1024 * 8192
    assert per_step == pytest.approx(20.874e12, rel=1e-4)


def test_qwen2_7b_2l_flops():
    dm = _dims("qwen2-7b-2l")
    assert (dm.layers, dm.d, dm.heads, dm.kv_heads, dm.head_dim, dm.d_ff,
            dm.vocab, dm.qkv_bias, dm.tied) == \
        (2, 3584, 28, 4, 128, 18944, 19200, True, False)
    # 2 x 233,046,016 per layer + 19200*3584 (the untied head; the input
    # embedding is a gather)
    assert dense.matmul_params(dm) == 2 * 233_046_016 + 68_812_800
    per_step = dense.train_flops_per_token(dm, 1024) * 8 * 1024
    assert per_step == pytest.approx(27.013e12, rel=1e-4)


def test_serving_work_counts():
    dm = _dims("smollm-360m")
    # one token of each of 2 sequences against 100 + 300 positions
    assert dense.forward_flops(dm, 2, 400) == \
        2.0 * dense.matmul_params(dm) * 2 + 4.0 * 32 * 15 * 64 * 400
    # K and V of 400 positions x 5 heads x 64 in bf16, and 2 queries and
    # outputs of 15 x 64, in each of 32 layers
    assert dense.paged_attention_bytes(dm, 2, 400, 2, 2) == \
        32 * (2 * 400 * 5 * 64 * 2 + 2 * 2 * 15 * 64 * 2)


def test_serving_tails_are_over_every_turn():
    """A p95 of all turns, not a median of waves' p95s; a rate of every
    token over the whole window."""
    from chipbench.drivers.serve import latency_metrics
    turns = [{"submitted": 0.0, "first": 0.01 * i, "answer": [0] * 11}
             for i in range(1, 101)]
    m = latency_metrics(turns, window_s=2.0)
    assert m["ttft_p95_ms"] == pytest.approx(950.5)
    assert m["decode_tokens_per_s"] == pytest.approx(1100 / 2.0)


def test_admission_order_is_the_same_lengths_for_every_seed():
    """Turn indices interleave whatever the seed; only which session of
    an index comes first changes."""
    import numpy as np

    from chipbench.drivers.serve import admission_order
    seen = set()
    for seed in (1, 2, 3):
        order = admission_order(48, 4, 1, np.random.default_rng(seed))
        assert sorted(order) == list(range(48))
        assert [(i + 1) % 4 for i in order] == [0, 1, 2, 3] * 12
        seen.add(tuple(order))
    assert len(seen) == 3


def test_loop_stall_from_the_pipeline_times():
    """Steps of 0.4 s run back to back after the syncs at steps 10 and
    20; the loop dispatches steps 21-25 at once, and the save after step
    25 holds it 6 s past the device's last step."""
    from chipbench.drivers.train import loop_stall
    starts = [0.0] * 40
    for i in range(10, 21):
        starts[i] = 4.0 + 0.4 * (i - 10)          # aligned with the device
    for i in range(21, 25):
        starts[i] = starts[20] + 0.001 * (i - 20)  # dispatch runs ahead
    starts[25] = starts[20] + 5 * 0.4 + 6.0
    assert loop_stall(starts, 25, [1, 10, 20, 30], 3) == pytest.approx(6.0)
    # no sync in the window before the one before the save: nothing
    assert loop_stall(starts, 25, [1, 20, 30], 3) is None
    assert loop_stall(starts, 5, [1, 10], 3) is None


def _run(**kw):
    # 20 calls of the step, 0.3 s each, and 3 of another program
    calls = [("jit_train_step", i * 4e8, i * 4e8 + 3e8) for i in range(20)]
    calls += [("jit_other", 9e9, 9.1e9)] * 3
    trace = Summary(window_s=10.0, busy_s=7.5, devices=1, op_s={},
                    idle_gaps=[], calls=calls)
    base = dict(window_steps=20, tokens_per_step=8192, flops_per_step=2e13,
                pipeline_wait_s=[0.001, 0.003], save_stall_s=2.0,
                save_commit_s=[20.0, 24.0], trace=trace,
                peaks={"bf16_flops_per_s": 2e14})
    base.update(kw)
    return RunData(**base)


def test_serving_metrics_arithmetic():
    read = lambda name, run: spec.metric_reader(name).read(run)  # noqa
    dm = _dims("smollm-360m")
    kernel = "custom-call(), custom_call_target=\"tpu_custom_call\""
    # two decode calls of 10 ms, each running 2 ms of kernel, one prefill
    trace = Summary(window_s=1.0, busy_s=0.03, devices=1, op_s={},
                    idle_gaps=[], calls=[("jit__unknown", 0, 10e6),
                           ("jit__unknown", 20e6, 30e6),
                           ("jit__unknown", 40e6, 50e6)],
                    kernels=[(kernel, 1e6, 3e6), (kernel, 21e6, 23e6)])
    steps = [(48, 48 * 700), (48, 48 * 701)]
    run = RunData(add_s=[0.02, 0.04], prompt_tokens=4096, pages_shared=224,
                  page_tokens=16, steps=steps, dims=dm, family=dense,
                  trace=trace,
                  peaks={"bf16_flops_per_s": 2e14, "hbm_bytes_per_s": 8e11})
    assert read("prefill_ms.serve", run) == pytest.approx(30.0)
    assert read("prefix_shared_share.serve", run) == pytest.approx(87.5)
    assert read("decode_step_device_ms.serve", run) == pytest.approx(10.0)
    moved = sum(dense.paged_attention_bytes(dm, b, c, 2, 2) for b, c in steps)
    assert read("paged_attention_roofline.serve", run) == \
        pytest.approx(100 * moved / 8e11 / 0.004)
    flops = sum(dense.forward_flops(dm, b, c) for b, c in steps)
    assert read("mfu.serve", run) == pytest.approx(100 * flops / 0.02 / 2e14)
    assert read("device_idle_share.serve", run) == pytest.approx(97.0)
    run.trace.kernels = []
    assert read("paged_attention_roofline.serve", run) is None
    assert read("decode_step_device_ms.serve", run) is None
    assert read("mfu.serve", run) is None


def test_train_metrics_arithmetic():
    read = lambda name, run: spec.metric_reader(name).read(run)  # noqa
    run = _run()
    # 20 steps of 2e13 FLOP over the whole 10 s window at 2e14 FLOP/s
    assert read("mfu.train", run) == pytest.approx(20.0)
    assert read("device_idle_share.train", run) == pytest.approx(25.0)
    assert read("step_device_ms.train", run) == pytest.approx(300.0)
    assert read("pipeline_wait_ms.train", run) == pytest.approx(2.0)
    assert read("save_stall_s.train", run) == pytest.approx(2.0)
    assert read("save_commit_s.train", run) == pytest.approx(22.0)


@pytest.mark.parametrize("base", ["pipeline_wait_ms", "step_device_ms",
                                  "mfu", "device_idle_share"])
def test_save_cycle_readers_read_as_the_train_ones(base):
    read = lambda name, run: spec.metric_reader(name).read(run)  # noqa
    run = _run()
    assert read(f"{base}.save_cycle", run) == read(f"{base}.train", run)


def test_metrics_with_nothing_to_read_are_left_out():
    read = lambda name, run: spec.metric_reader(name).read(run)  # noqa
    run = _run(save_stall_s=None, save_commit_s=[], peaks=None)
    assert read("save_stall_s.train", run) is None
    assert read("save_commit_s.train", run) is None
    assert read("mfu.train", run) is None
    run.trace.calls = [c for c in run.trace.calls if c[0] == "jit_other"]
    assert read("step_device_ms.train", run) is None
