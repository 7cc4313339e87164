"""chip_smoke.py's phases at ``gpt_tiny`` on the CPU.

The script itself runs on a TPU or not at all; its phases are plain
functions over a model builder and sizes, and this drives the same code
small, with the Pallas kernels in interpret mode and without the
"lowered step holds the Mosaic custom call" checks that only a TPU
lowering can meet.  What it pins here: the checks pass on a healthy
tree, a failed check is counted, and ``main`` refuses to run without
the chip.
"""

import chip_smoke
from paddle_tpu.models.gpt import gpt_tiny


def test_phases_pass_at_gpt_tiny_on_cpu():
    report = chip_smoke.Report()
    chip_smoke.flash_parity(report, shape=(1, 128, 2, 16), on_chip=False)
    losses = chip_smoke.train_phase(report, gpt_tiny, batch=2, seq=32,
                                    steps=3, on_chip=False)
    assert len(losses) == 3
    chip_smoke.ragged_parity(report, num_heads=4, head_dim=16,
                             block_size=16, on_chip=False)
    logprobs = chip_smoke.serve_phase(
        report, gpt_tiny, dtype="float32", max_model_len=64,
        token_budget=16, prompt_lens=[5, 9, 20, 30], max_new_tokens=6,
        logprob_tol=1e-4, on_chip=False)
    assert [len(ids) for ids, _ in logprobs] == [6, 6, 6, 6]
    chip_smoke.compare_engines(report, "self", logprobs, logprobs, 0.0)
    assert report.failed == []


def test_a_failed_check_and_a_raising_phase_are_counted():
    report = chip_smoke.Report()
    report.check("p", "arithmetic", 1 + 1 == 3)

    def boom(_report):
        raise ValueError("boom")

    assert report.run("q", boom) is None
    assert report.failed == ["p: arithmetic", "q: ran to the end"]


def test_main_refuses_without_a_tpu(capsys):
    assert chip_smoke.main() != 0
    out, err = capsys.readouterr()
    assert out == ""                    # no result line
    assert "no TPU" in err and "'cpu'" in err
