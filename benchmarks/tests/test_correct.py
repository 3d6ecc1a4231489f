"""``correct`` comes out true for the program as it is and false for the
control and for each fault the cells can have. On the CPU, through the
rehearsal mode of ``run_cell``: the harness's look for a chip is skipped
and everything else of a run is driven, at tiny sizes, with the timed
path broken underneath where a test says so.

Slow for unit tests (each run compiles the encoder for the CPU); they
are the benchmark's own and not part of the repo's tier-1 run.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks.lib import runner, spec, system
from benchmarks.lib.traffic import make_traffic
from benchmarks.lib.weights import make_weights

TINY = {
    "rows": 3000,
    "pool_docs": 256,
    "index.reserved_space": 4096,
    "fill_chunk": 1024,
    "correct.sample_queries": 64,
    "correct.min_fresh": 1,
    "mix.writer.batch": 32,
    "mix.queries.clients": 4,
}
SECONDS = 4.0


def rehearse(cell_name="doc-l6.serve", seed=5, control=None):
    cell = spec.load_cell(cell_name)
    return runner.run_cell(cell, seed, SECONDS, False, control=control, rehearsal=TINY)


def test_sound_run_is_correct_and_its_control_is_not():
    result = rehearse(control="fp8")
    assert result["correct"], result["check"]
    assert result["metrics"] == {} and result["device"]["platform"] == "cpu"  # never a device metric
    assert list(result)[-1] == "check"
    assert result["check"]["fresh_checked"][0] >= TINY["correct.min_fresh"]
    assert result["control"]["fails"], result["control"]
    limits = spec.load_cell("doc-l6.serve").config["correct"]["limits"]
    assert result["control"]["score_err"] > 3 * result["check"]["score_err"][0]
    assert result["control"]["score_err"] > limits["score_err"]


def test_second_cell_rehearses_correct():
    assert rehearse("doc-l12.backfill", seed=2**31 + 77)["correct"]


def _skip_the_add(self, keys, texts):
    self.data_embed(texts)  # the state comes back unchanged


def _add_half(self, keys, texts):
    half = len(keys) // 2
    self.index.add_batch_device(keys[:half], self.data_embed(texts[:half]), None)


def _alter_an_answer(original):
    def search(self, texts):
        answers = original(self, texts)
        return [[((a[0][0] + 1234) % 2000, a[0][1])] + list(a[1:]) for a in answers]

    return search


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch", "answer_altered"])
def test_fault_in_the_timed_path_is_not_correct(monkeypatch, fault):
    if fault == "state_unchanged":
        monkeypatch.setattr(system.System, "embed_and_add", _skip_the_add)
    elif fault == "half_batch":
        monkeypatch.setattr(system.System, "embed_and_add", _add_half)
    else:
        monkeypatch.setattr(system.System, "search", _alter_an_answer(system.System.search))
    result = rehearse()
    assert not result["correct"]
    failed = {name for name, (value, limit) in result["check"].items() if name in ("rank_gap", "score_err") and value > limit}
    assert failed, result["check"]


def test_the_command_as_the_driver_runs_it_needs_a_tpu():
    root = spec.ROOT
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "doc-l6.serve", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=root,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "needs 1 TPU chip" in proc.stderr


def test_reference_agrees_with_the_program_at_a_small_size():
    """The reference's tokenizer gives the program's ids, and its float32
    encoder the program's plain-XLA encoder's embeddings to bfloat16's
    grade, on the seed's weights."""
    import dataclasses

    import jax

    from pathway_tpu.models.encoder import TextEncoder
    from pathway_tpu.models.sentence_encoder import SentenceEncoder

    cell = spec.load_cell("doc-l6.serve")
    config = dict(cell.config, pool_docs=64, rows=64)
    traffic = make_traffic(config, cell.traffic | {"writer": {"batch": 32}}, 11)
    texts = traffic.pool_texts[:24] + ["Punctuation, too: (yes) -- it's split!", "", "MiXed Case 123abc"]
    enc = SentenceEncoder(config["model"]["name"])
    got = enc.tokenizer.batch_encode_matrix(texts, enc.max_seq_len)
    ids, lens = cell.family.tokenize(texts, config["model"])
    assert (np.asarray(got[1]) == lens).all() and (np.asarray(got[0]) == ids).all()

    weights = make_weights(cell.family, config["model"], config["weights"], 11)
    params = system._lay_over(enc.params, weights)
    module = TextEncoder(dataclasses.replace(enc.cfg, layer_impl="xla", attention_impl="xla"))
    mask = np.arange(ids.shape[1])[None, :] < lens[:, None]
    theirs = np.asarray(jax.jit(module.apply)(params, ids, mask))
    ours = np.asarray(cell.family.encode(weights, config["model"], texts))
    assert (theirs * ours).sum(axis=1).min() > 0.9999
    assert np.abs(theirs - ours).max() < 5e-3
    # unrelated documents are far apart: the cure for collinear seeded embeddings holds
    cos = ours[:24] @ ours[:24].T
    assert np.abs(cos - np.eye(24)).max() < 0.7
