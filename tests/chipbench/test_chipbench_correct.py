"""What decides ``correct`` has been shown to fail: the control (the
reference in the nearest precision below the configuration's) and each
fault a training cell can have, at a size a test run can hold. The harness's
look for a chip is skipped; the rest of a run is driven as it is."""
import jax
import jax.numpy as jnp
import pytest

from chipbench import check, run
from chipbench.pipelines import common, token_decoder

CELL = "mistral7b-tok4k-1chip"


class StateUnchanged(token_decoder.Job):
    """A step that returns its state as it got it."""

    def step(self, batch):
        copy = jax.tree.map(jnp.copy, (self.params, self.opt))
        return self._step(*copy, batch["token"])[2]


class HalfBatch(token_decoder.Job):
    """Half of the batch left out, the mean taken over the rest."""

    def step(self, batch):
        tokens = batch["token"]
        half = tokens.shape[0] // 2
        return super().step({**batch, "token": jnp.concatenate(
            [tokens[:half], tokens[:half]])})


class TokenAltered(token_decoder.Job):
    """An answer altered where it is produced: one staged token."""

    def next_batch(self):
        batch = super().next_batch()
        return {**batch, "token": batch["token"].at[1, 7].add(1)}


def drive(job_class, tmp_path, seed=5):
    bench, cell, config, traffic = run.load_cell(CELL, rehearsal=True)
    job = job_class(config, traffic, jax.devices()[:1], seed,
                    str(tmp_path / "store"))
    lines = []
    result = run.drive(job, cell=cell, bench=bench, seconds=0.5, trace=False,
                       seed=seed, device={"platform": "cpu", "kind": "cpu",
                                          "count": 1},
                       emit=lines.append, started_at=0.0, rehearsal=True)
    return result


def test_a_sound_run_is_correct(tmp_path):
    result = drive(token_decoder.Job, tmp_path)
    assert result["correct"] is True, result["compared"]


@pytest.mark.parametrize("fault,caught_by", [
    (StateUnchanged, "update_norm_gap"), (HalfBatch, "grad_norm_gap"),
    (TokenAltered, "staged_elements_wrong")])
def test_a_broken_timed_path_is_not_correct(tmp_path, fault, caught_by):
    result = drive(fault, tmp_path)
    assert result["correct"] is False
    caught = result["compared"][caught_by]
    assert caught["value"] > caught["limit"], result["compared"]


def test_the_control_in_lower_precision_is_not_correct(tmp_path):
    _, cell, config, traffic = run.load_cell(CELL, rehearsal=True)
    limits = check.load_limits(run.ROOT, CELL, rehearsal=True)
    job = token_decoder.Job(config, traffic, jax.devices()[:1], 5,
                            str(tmp_path / "store"))
    first = run.first_steps(job, lambda line: None, 0.0)
    keys = [run.np_tree(k) for k in first["keys"]]
    job.free()
    reference = job.reference(keys)
    sound, _ = check.verdict(check.training_numbers(first["program"],
                                                    reference), limits)
    control, compared = check.verdict(check.training_numbers(
        job.reference(keys, precision="float8_e4m3fn"), reference), limits)
    assert sound is True and control is False, compared


def test_gaps_are_of_norms_against_the_larger_of_leaf_and_median():
    reference = {"a": 1.0, "b": 2.0, "c": 1e-9}
    program = {"a": 1.1, "b": 2.0, "c": 2e-9}
    gap, leaf = check.worst_gap(check.leaf_gaps(program, reference))
    assert leaf == "a" and gap == pytest.approx(0.1)   # c is held to the median
    assert check.moved_leaves({"a": 1.0, "b": 2.0, "c": 1e-9}) == ["a", "b"]
    worst, where = check.worst_gap(check.leaf_gaps(
        {"a": float("nan"), "b": 2.0, "c": 0}, reference))
    assert where == "a" and worst != worst
    ok, compared = check.verdict({"x": float("nan"), "_note": 1}, {})
    assert ok is False and "_note" not in compared


def test_image_pipeline_on_four_devices_control_and_faults_are_not_correct(
        tmp_path):
    """The image pipeline over four (virtual) devices, as the four-chip cell
    that PERF.md keeps for later will run it: the reference put in the
    program's place in fp8, with half of the batch left out, and with one
    device's rows alone (what a missing exchange between chips leaves) each
    fails one of the numbers the image cell is held to."""
    from chipbench.pipelines import image_classifier
    cell_name = "rn50-jpeg224-1chip"
    _, cell, config, traffic = run.load_cell(cell_name, rehearsal=True)
    limits = check.load_limits(run.ROOT, cell_name, rehearsal=True)
    job = image_classifier.Job(config, traffic, jax.devices()[:4], 7,
                               str(tmp_path / "store"))
    job.write_store()
    job.start()
    batches = [job.next_batch() for _ in range(2)]
    assert all(common.staged_layout_faults(b, 4) == 0 for b in batches)
    assert common.staged_layout_faults(batches[0], 2) > 0
    keys = [job.host_copy(b)["id"] for b in batches]
    job.free()
    reference = job.reference(keys)
    per_device = job.global_batch // 4
    for plant in (dict(precision="float8_e4m3fn"),
                  dict(rows=job.global_batch // 2), dict(rows=per_device)):
        ok, compared = check.verdict(check.training_numbers(
            job.reference(keys, **plant), reference), limits)
        assert ok is False, (plant, compared)
    same, _ = check.verdict(check.training_numbers(reference, reference),
                            limits)
    assert same is True
