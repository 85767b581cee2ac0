"""The comparison that decides ``correct`` has been shown to fail: at tiny
sizes on the CPU, the control (the reference one precision step down, in
the program's place) and each fault a serving cell can have (an answer or a
token altered where it is produced) come out as not correct under the
shipped limits, while the unbroken path comes out correct. The harness's
look for a chip is skipped (``rehearse``); the rest of a run is driven."""

import pytest

from benchmark import cells, run


def drive(mix: str, break_it=None, seconds: float = 2.0):
    cell = cells.Cell(f"rehearsal-tiny.{mix}", rehearse=True)
    bench = run.Bench(cell, rehearse=True)
    try:
        bench.setup(2**31 + 21)
        if break_it:
            break_it(bench.handle)
        result = bench.window(seconds)
        sample = bench.sample(result)
    finally:
        bench.teardown()
    return cell, bench, result, sample


def verdict(cell, bench, result, sample, control=False):
    numbers = bench.compare(sample, control=control)
    return run.judge(numbers, run.limits(cell), result["client"])


def alter_embedding(handle):
    """An answer altered where it is produced: the tower's output loses its
    sign on half of its dimensions."""
    mgr = handle.services["clip"].managers["clip"]
    inner = mgr._encode_images

    def broken(params, pixels):
        z = inner(params, pixels)
        return z.at[:, : z.shape[1] // 2].multiply(-1.0)

    mgr._encode_images = broken


def alter_tokens(handle):
    """A token altered where it is produced: every fifth token the engine
    emits is replaced by its neighbour in the vocabulary."""
    engine = handle.services["vlm"].manager._pick_engine()
    inner = engine.submit_stream

    def broken(req):
        for i, tok in enumerate(inner(req)):
            yield tok + 1 if i % 5 == 4 else tok

    engine.submit_stream = broken


@pytest.fixture(scope="module")
def clip_run():
    return drive("import_bulk")


@pytest.fixture(scope="module")
def vlm_run():
    return drive("caption_storm", seconds=3.0)


def test_clip_sound_run_is_correct_and_the_control_reads_above_an_exact_run(clip_run):
    """At these sizes the rounding of JPEG decoding and resizing (6e-5) hides
    what bfloat16 costs (1.5e-5), so the control cannot fail the shipped tiny
    limits; that it fails at the cells' own sizes is read on the chip
    (PERF.md). Here: the sound run passes, and the control reads far above a
    run that is exact (the reference's own vectors in the program's place)."""
    import numpy as np

    from benchmark.references import clip as ref

    cell, bench, result, sample = clip_run
    ok, compared = verdict(cell, bench, result, sample)
    assert ok, compared
    control = bench.compare(sample, control=True)
    size = cell.config["models"]["clip"]["config"]["vision_config"]["image_size"]
    pixels = np.stack([ref.preprocess(j, size) for j in sample["jpegs"]])
    import os

    model_dir = os.path.join(run.CACHE, "models", bench.names["clip"])
    exact = bench.compare({**sample, "served": ref.embed_images(model_dir, cell.config["models"]["clip"]["config"], pixels)})
    assert exact["embed_cos_gap"] < 1e-6 < control["embed_cos_gap_median"]


def test_vlm_sound_run_is_correct_and_the_control_is_not(vlm_run):
    ok, compared = verdict(*vlm_run)
    assert ok, compared
    ok, compared = verdict(*vlm_run, control=True)
    assert not ok, compared


def test_an_altered_embedding_is_not_correct():
    ok, compared = verdict(*drive("import_bulk", alter_embedding))
    assert not ok and compared["embed_cos_gap"]["value"] > compared["embed_cos_gap"]["limit"]


def test_an_altered_token_is_not_correct():
    ok, compared = verdict(*drive("caption_storm", alter_tokens, seconds=3.0))
    assert not ok and compared["logit_gap_std"]["value"] > compared["logit_gap_std"]["limit"]


def test_a_failed_request_is_not_correct(clip_run):
    cell, bench, result, sample = clip_run
    broken = {**result["client"], "failed": 1}
    ok, _ = run.judge({"embed_cos_gap": 0.0, "embed_cos_gap_median": 0.0}, run.limits(cell), broken)
    assert not ok
