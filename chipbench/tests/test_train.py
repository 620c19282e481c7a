"""A whole run of the tiny training cell on the CPU, the chip check
skipped: the sound program comes out correct; a step that returns its state
unchanged, and a step that leaves half of the batch out, come out not
correct; judged by the harness's own decision, the bfloat16-state control
and the reference with half of each batch left out come out not correct
where the sound program comes out correct."""
import jax
import pytest

from chipbench.lib import bench, counts, train
from chipbench.tests import tiny

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CELL = "tiny.train"


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return tiny.make_checkout(tmp_path_factory.mktemp("checkout"))


@pytest.fixture(autouse=True)
def cpu_peaks(monkeypatch):
    monkeypatch.setattr(counts, "peaks", lambda kind: PEAKS)


def run(checkout, seed, seconds=1.0):
    c = bench.load_cell(checkout, CELL)
    args = bench.parse_args(["--workload", CELL, "--seed", str(seed),
                             "--seconds", str(seconds)])
    return train.run(c, args, jax.devices(), bench.CompileClock(), 0.0)


def broken(monkeypatch, fault):
    """The program's ``make_train_step`` with ``fault`` wrapped round it."""
    import repro.train
    real = repro.train.make_train_step
    monkeypatch.setattr(repro.train, "make_train_step",
                        lambda cfg, tcfg: fault(real(cfg, tcfg)))


def test_sound_run_is_correct(checkout):
    result, checks = run(checkout, 2 ** 33 + 5)
    assert result["correct"], checks
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert list(checks) == ["loss_gap", "grad_norm_gap", "update_norm_gap"]


def test_state_left_unchanged_is_not_correct(checkout, monkeypatch):
    def frozen(step):
        def f(params, opt, batch, i):
            return (params, opt) + step(params, opt, batch, i)[2:]
        return f

    broken(monkeypatch, frozen)
    result, checks = run(checkout, 21)
    assert not result["correct"]
    assert checks["update_norm_gap"]["value"] == pytest.approx(1.0)


def test_half_batch_left_out_is_not_correct(checkout, monkeypatch):
    def half(step):
        def f(params, opt, batch, i):
            n = batch["tokens"].shape[0] // 2
            return step(params, opt, {k: v[:n] for k, v in batch.items()}, i)
        return f

    broken(monkeypatch, half)
    result, checks = run(checkout, 22)
    assert not result["correct"]
    assert checks["loss_gap"]["value"] > checks["loss_gap"]["limit"]


def test_control_lies_past_the_limit(checkout):
    c = bench.load_cell(checkout, CELL)
    for seed in (5, 6, 7):
        args = bench.parse_args(["--workload", CELL, "--seed", str(seed),
                                 "--seconds", "0.5"])
        r = train.limit_readings(c, args, jax.devices(),
                                 bench.CompileClock(), control=True)
        assert bench.passes(r["program"]), r["program"]
        assert not bench.passes(r["control"]), r["control"]
        assert not bench.passes(r["half_batch"]), r["half_batch"]
