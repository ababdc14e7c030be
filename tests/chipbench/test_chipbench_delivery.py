"""The store's guarantees as the comparison holds a run to them: seeded
stores, delivery counted row group by row group, staged bytes against an
independent decode. No model is compiled here."""
import jax
import numpy as np
import pytest

from chipbench import run, stores
from chipbench.pipelines import common, image_classifier


def epochs(n_groups, n_epochs, seed=0):
    rng = np.random.default_rng(seed)
    return np.concatenate([rng.permutation(n_groups) for _ in range(n_epochs)])


def test_sound_delivery_reads_zero_everywhere():
    assert set(common.delivery_numbers(epochs(32, 5), 32).values()) == {0}
    # Neighbouring epochs may interleave at the boundary (sample_order=free).
    stream = epochs(32, 4)
    stream[[31, 32]] = stream[[32, 31]]
    assert set(common.delivery_numbers(stream, 32).values()) == {0}


@pytest.mark.parametrize("fault,number", [
    ("dropped", "groups_imbalance"), ("echoed", "epochs_short"),
    ("unshuffled", "epochs_unshuffled")])
def test_a_broken_guarantee_reads_above_zero(fault, number):
    stream = epochs(32, 6)
    if fault == "dropped":
        stream = stream[stream != 7]
    elif fault == "echoed":
        stream = np.repeat(stream, 2)
    else:
        stream = np.tile(np.arange(32), 6)
    assert common.delivery_numbers(stream, 32)[number] > 0


@pytest.fixture(scope="module")
def image_job(tmp_path_factory):
    _, _, config, traffic = run.load_cell("rn50-jpeg224-1chip", rehearsal=True)
    job = image_classifier.Job(config, traffic, jax.devices()[:1], 2147483653,
                               str(tmp_path_factory.mktemp("store") / "s"))
    job.write_store()
    job.start()
    yield job
    job.free()


def test_image_store_is_a_function_of_the_seed(image_job, tmp_path):
    c = image_job.cfg
    for seed, same in ((image_job.seed, True), (image_job.seed + 1, False)):
        stores.write_image_store(str(tmp_path / "again"), c["store_rows"],
                                 c["num_classes"], seed, c["image_size"],
                                 c["rows_per_row_group"], c["jpeg_quality"])
        a = stores.read_columns(str(tmp_path / "again"), ["image", "label"])
        b = stores.read_columns(image_job.store_path, ["image", "label"])
        assert (a["image"] == b["image"]) is same


def test_staged_batches_are_the_stored_bytes_and_every_row_arrives(image_job):
    batches = [image_job.host_copy(image_job.next_batch()) for _ in range(16)]
    assert batches[0]["image"].shape == (8, 32, 32, 3)
    assert all(image_job.staged_faults(b) == 0 for b in batches)
    numbers = image_job.delivery([b["id"] for b in batches])
    assert set(numbers.values()) == {0}, numbers
    altered = dict(batches[0], image=batches[0]["image"].copy())
    altered["image"][3, 5, 5, 1] ^= 1
    assert image_job.staged_faults(altered) == 1
    swapped = [b["id"] for b in batches]
    swapped[2] = swapped[2][::-1].copy()
    assert image_job.delivery(swapped)["rows_out_of_group"] > 0


def test_token_store_round_trip(tmp_path):
    stores.write_token_store(str(tmp_path / "t"), 4, 16, 100, 2147483653)
    cols = stores.read_columns(str(tmp_path / "t"), ["ts", "token"])
    assert np.array_equal(cols["ts"], np.arange(64))
    assert cols["token"].dtype == np.int32 and cols["token"].max() < 100
    again = np.random.default_rng(2147483653).integers(0, 100, 64)
    assert np.array_equal(cols["token"], again)
