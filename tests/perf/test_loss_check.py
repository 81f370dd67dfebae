"""The loss check of ``correct`` (``perf/run.py`` ``loss_verdict``) on
losses recorded on the chip, on fault trajectories made for the purpose,
and the traffic's entropy it stands on.

``data/loss_trajectories.jsonl``: every step's loss of the accepted tree
(commit 02a1a51) in the four cells, one run a line, TPU v5 lite, 20 s
(my chip runs, PR 29).  ``data/loss_faults.json``: the trajectories of
programs broken on purpose (scratch copies, never the repository), a toy
cell on the CPU and one chip run at the real size.
"""

import json
import math
import statistics
from pathlib import Path

import numpy as np
import pytest

from perf import run
from perf.traffic import zipf_tokens

REPO = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
CELLS = {"gpt2-large.s1024": 24, "gpt2-large.gas4": 24,
         "gpt2-large.s128": 12, "gpt2-xl.z3x4": 8}
# the one-step bands the cells' files held until PR 29 (step, low, high);
# how the settled median's scatter over seeds compares with that one
# step's (a tenth in the cell that reaches the plateau, no better in the
# two whose traced runs end at step 18, still on the descent); and how
# many of this file's seeds, drawn before any was read, the band refuses
OLD_RULE = {"gpt2-large.s1024": (16, 7.5, 8.7, 0.1, 1),
            "gpt2-large.gas4": (12, 8.1, 9.1, 1.0, 1),
            "gpt2-xl.z3x4": (12, 8.0, 9.4, 1.1, 0)}


def _json(*parts):
    return json.loads(REPO.joinpath(*parts).read_text())


def _cell(name):
    """(the cell's loss_check, its traffic's entropy, the scatter of one
    reported loss): what ``run_cell`` hands ``loss_verdict``."""
    cell = _json("perf", "workloads", name + ".json")
    config = _json("perf", "configs", cell["config"] + ".json")
    traffic = _json("perf", "traffic", cell["traffic"] + ".json")
    tokens = traffic["batch_per_chip"] * cell["chips"] * traffic["seq"]
    vocab = config["vocab_size"]
    return (cell["loss_check"], zipf_tokens.entropy(traffic, vocab),
            zipf_tokens.batch_sd(traffic, vocab, tokens))


def _judged(name):
    """A cell's recorded runs, each with its ``verdict`` under the cell's
    file, after (loss_check, entropy, sd)."""
    check, entropy, sd = _cell(name)
    with open(DATA / "loss_trajectories.jsonl") as f:
        runs = [json.loads(line) for line in f]
    return check, entropy, sd, [
        dict(r, verdict=run.loss_verdict(r["losses"], check, entropy, sd))
        for r in runs if r["cell"] == name]


def _by_seed(runs):
    """{seed: the settled statistic of each of its runs}"""
    by_seed = {}
    for r in runs:
        by_seed.setdefault(r["seed"], []).append(r["verdict"]["settled"])
    return by_seed


def _needed_rise(statistics_of_seeds, entropy):
    """How ``rise`` is set: the limit lies above the worst seed by the
    larger of 6 sample sd and that seed's distance from the seeds'
    median (so twice that distance above the median)."""
    worst = max(statistics_of_seeds)
    return worst - entropy + max(
        6 * statistics.stdev(statistics_of_seeds),
        worst - statistics.median(statistics_of_seeds))


@pytest.mark.parametrize("name", CELLS)
def test_recorded_runs_of_the_accepted_tree_pass_with_room(name):
    check, entropy, sd, runs = _judged(name)
    assert all(r["verdict"]["ok"] for r in runs)
    # parity, finite losses and no compile in the window, as run.py said
    assert all(r["rest_held"] for r in runs)
    by_seed = _by_seed(runs)
    assert len(by_seed) >= CELLS[name]
    # the rise is the rule's, rounded up to 0.05: neither tighter nor,
    # by more than that rounding, looser
    needed = _needed_rise([s[0] for s in by_seed.values()], entropy)
    assert needed <= check["rise"] < needed + 0.05 + 1e-9
    # and the limit lies under ln V, what a program that has learnt
    # nothing reports, by the scatter the floor allows a batch
    assert entropy + check["rise"] < math.log(50257) - run.FLOOR_SDS * sd
    # the floor fitted nothing, and no sound loss comes near it
    assert min(r["verdict"]["lowest"] for r in runs) > entropy - 2 * sd
    # the last index is reached with two steps to spare by the shortest
    # runs, the traced ones
    lengths = {trace: min(len(r["losses"]) for r in runs
                          if r["trace"] == trace) for trace in (0, 1)}
    assert sum(r["trace"] for r in runs) >= 2
    assert check["steps"][1] + 2 <= lengths[1] <= lengths[0]


@pytest.mark.parametrize("name", CELLS)
def test_a_seed_gives_one_statistic_traced_or_not(name):
    """Fixed indices: the same seed's median is the same number in a
    traced run, an untraced one and a second untraced one (the recorded
    losses agree to the fourth decimal, every step)."""
    repeated = [s for s in _by_seed(_judged(name)[3]).values() if len(s) > 1]
    assert len(repeated) >= 2
    for settled in repeated:
        assert max(settled) - min(settled) < 1e-3


@pytest.mark.parametrize("name", OLD_RULE)
def test_the_old_one_step_band_left_a_sound_seed_no_room(name):
    """Documented regression, not a rule in force: the loss at ONE step
    of the descent, inside a band made from the range of a dozen seeds,
    put a sound seed of the accepted tree within 0.07 of an edge (and
    refused PR 27 and PR 28 on the parent's side); the settled median of
    the same runs has the rule's room: the worst seed is as far inside
    the limit as it is from the seeds' median."""
    step, low, high, scatter, refused = OLD_RULE[name]
    check, entropy, _, runs = _judged(name)
    at = {r["seed"]: r["losses"][step - 1] for r in runs}.values()
    assert min(min(at) - low, high - max(at)) < 0.07
    assert sum(not low <= loss <= high for loss in at) == refused
    settled = [s[0] for s in _by_seed(runs).values()]
    room = entropy + check["rise"] - max(settled)
    assert room > 0.07
    assert room >= max(settled) - statistics.median(settled)
    assert statistics.stdev(settled) < scatter * statistics.stdev(at)


def _faults():
    return _json("tests", "perf", "data", "loss_faults.json")


def _toy():
    """(the toy cell's record, its entropy, a run's verdict under it)"""
    toy = _faults()["toy"]
    entropy = zipf_tokens.entropy(toy["traffic"], toy["vocab"])
    sd = zipf_tokens.batch_sd(toy["traffic"], toy["vocab"], toy["tokens"])
    return toy, entropy, lambda r: run.loss_verdict(
        r["losses"], toy["loss_check"], entropy, sd)


def test_the_toy_cells_sound_seeds_pass_under_the_same_rule():
    toy, entropy, judge = _toy()
    verdicts = [judge(r) for r in toy["sound"]]
    assert len(verdicts) >= 12 and all(v["ok"] for v in verdicts)
    needed = _needed_rise([v["settled"] for v in verdicts], entropy)
    assert needed <= toy["loss_check"]["rise"] < needed + 0.05 + 1e-9


def _refusal(verdict):
    return {"settled": verdict["settled"] is not None
            and verdict["settled"] > verdict["limit"],
            "floor": verdict["lowest"] < verdict["floor"]}


@pytest.mark.parametrize("fault, refused_by", [
    ("apply_changes_nothing", "settled"),
    ("update_of_the_wrong_sign", "settled"),
    ("labels_not_shifted", "floor"),
])
def test_a_broken_program_is_refused_on_the_toy_cell(fault, refused_by):
    toy, _, judge = _toy()
    runs = [r for r in toy["faults"] if r["fault"] == fault]
    assert len(runs) >= 2
    for r in runs:
        verdict = judge(r)
        assert not verdict["ok"]
        assert _refusal(verdict)[refused_by]
        assert r["refused_by"] == refused_by


def test_ten_times_the_learning_rate_is_refused_at_the_real_size():
    """On the chip: AdamW at 2.5e-3 on the fresh 774M model spikes to 14,
    learns, and is still 0.4 above the sound seeds at steps 25 to 56."""
    chip = _faults()["chip"]
    check, entropy, sd = _cell(chip["cell"])
    runs = [r for r in chip["faults"]
            if r["fault"] == "learning_rate_times_10"]
    assert len(runs) >= 2
    for r in runs:
        verdict = run.loss_verdict(r["losses"], check, entropy, sd)
        assert not verdict["ok"] and r["refused_by"] == "settled"
        assert verdict["settled"] - verdict["limit"] > 0.2
        assert not _refusal(verdict)["floor"]


def test_ten_times_the_learning_rate_is_no_fault_at_toy_size():
    """Kept so that nobody reads the toy cell as proof of this one: two
    layers of width 64 learn a unigram at 1e-2 as they do at 1e-3, the
    run settles where the sound seeds do, and the check passes it."""
    toy, _, judge = _toy()
    runs = [r for r in toy["faults"]
            if r["fault"] == "learning_rate_times_10"]
    assert len(runs) >= 2
    sound = [judge(r)["settled"] for r in toy["sound"]]
    for r in runs:
        verdict = judge(r)
        assert verdict["ok"] and r["refused_by"] is None
        assert verdict["settled"] < max(sound) + 0.02


def test_verdict_on_made_up_losses():
    check = {"steps": [4, 8], "rise": 0.5}
    sound = [10.0, 9.0, 8.5, 8.1, 8.0, 8.2, 8.0, 8.1, 8.0]
    verdict = run.loss_verdict(sound, check, entropy=7.8, sd=0.05)
    assert verdict == {"settled": 8.1, "limit": 8.3, "lowest": 8.0,
                       "floor": pytest.approx(7.5), "ok": True}
    # two spikes among five steps move no median
    spiked = sound[:4] + [12.0, 8.2, 11.0, 8.1, 8.0]
    assert run.loss_verdict(spiked, check, 7.8, 0.05)["ok"]
    # three do
    spiked[7] = 9.5
    assert not run.loss_verdict(spiked, check, 7.8, 0.05)["ok"]
    # a run that ends before the last index is not judged sound
    short = run.loss_verdict(sound[:7], check, 7.8, 0.05)
    assert short["settled"] is None and not short["ok"]
    # one loss under the floor, anywhere, refuses
    assert not run.loss_verdict(sound + [7.4], check, 7.8, 0.05)["ok"]
    assert run.loss_verdict(sound + [7.6], check, 7.8, 0.05)["ok"]
    # a loss that is no number is not sound
    assert not run.loss_verdict(sound[:5] + [math.nan] + sound[6:], check,
                                7.8, 0.05)["ok"]


@pytest.mark.parametrize("exponent, vocab", [(1.0, 7), (1.3, 250), (0.5, 32)])
def test_entropy_and_batch_sd_against_a_direct_sum(exponent, vocab):
    params = {"exponent": exponent}
    weights = [r ** -exponent for r in range(1, vocab + 1)]
    p = [w / sum(weights) for w in weights]
    entropy = -sum(q * math.log(q) for q in p)
    variance = sum(q * (math.log(q) + entropy) ** 2 for q in p)
    assert zipf_tokens.entropy(params, vocab) == pytest.approx(entropy,
                                                               rel=1e-9)
    assert zipf_tokens.batch_sd(params, vocab, 1) == pytest.approx(
        math.sqrt(variance), rel=1e-9)
    assert zipf_tokens.batch_sd(params, vocab, 400) == pytest.approx(
        math.sqrt(variance / 400), rel=1e-9)


def test_entropy_of_the_published_vocabulary_and_of_what_make_draws():
    traffic = _json("perf", "traffic", "zipf.b4.s1024.json")
    assert round(zipf_tokens.entropy(traffic, 50257), 4) == 7.5659
    assert round(zipf_tokens.batch_sd(traffic, 50257, 4096), 3) == 0.051
    assert round(zipf_tokens.batch_sd(traffic, 50257, 32768), 3) == 0.018
    # the drawn ids' own surprise, under the distribution, has that mean
    # and that scatter: the figure describes the traffic, not a formula
    params = {"exponent": 1.0, "pool_steps": 64, "seq": 512}
    ids = zipf_tokens.make(params, 8, 50257, seed=2147483659)
    ranks = np.arange(1, 50258, dtype=np.float64)
    surprise = np.log(ranks) + np.log(np.sum(1 / ranks))
    per_batch = surprise[ids].reshape(64, -1).mean(axis=1)
    sd = zipf_tokens.batch_sd(params, 50257, 8 * 512)
    assert abs(per_batch.mean() - zipf_tokens.entropy(params, 50257)) \
        < 4 * sd / 8
    assert 0.7 * sd < per_batch.std(ddof=1) < 1.3 * sd
