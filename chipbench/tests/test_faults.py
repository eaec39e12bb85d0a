"""A run with the timed path broken underneath comes out not correct:
once for each fault the cells can have.  The harness's look for a chip
is skipped; everything else is a whole run at a small size."""
import numpy as np

from chipbench.tests._cells import run_tiny


def test_chain_state_left_unchanged(monkeypatch):
    from repro.core import chain
    monkeypatch.setattr(chain.DeviceReferenceChain, "advance",
                        lambda self, dev, curr: None)
    assert not run_tiny("isabel.write")["correct"]


def test_half_of_each_step_left_out(monkeypatch):
    from repro.core import compress
    orig = compress._encode_topk

    def half(bin_ids, ids_desc, b_bits, k_eff, max_bins):
        idx = orig(bin_ids, ids_desc, b_bits, k_eff, max_bins)
        return idx.at[idx.shape[0] // 2:].set(0)
    monkeypatch.setattr(compress, "_encode_topk", half)
    assert not run_tiny("isabel.write")["correct"]


def test_one_answer_altered_where_it_is_produced(monkeypatch):
    from repro.core import pipeline
    orig = pipeline.finalize_step
    calls = []

    def altered(*args, **kw):
        step = orig(*args, **kw)
        calls.append(1)
        if len(calls) == 3:                 # one field-step of the run
            step.centers = step.centers + 4e-3
        return step
    monkeypatch.setattr(pipeline, "finalize_step", altered)
    assert not run_tiny("isabel.write")["correct"]


def test_restored_step_altered_on_its_way_to_the_device(monkeypatch):
    from repro.distributed import pipeline
    orig = pipeline.ShardedDecompressor.decompress

    def altered(self, step, prev):
        out = np.array(orig(self, step, prev))
        out.reshape(-1)[0] += 1.0
        return out
    monkeypatch.setattr(pipeline.ShardedDecompressor, "decompress", altered)
    assert not run_tiny("isabel.read")["correct"]
