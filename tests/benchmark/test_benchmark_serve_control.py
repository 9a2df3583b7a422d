"""The int8 control of the serving cells comes out not correct.

The number held is a gap between logits, so it has the scale of the logits,
and that grows with the model's width: at a toy width the int8 pass reads
under the real cell's limit for no better reason than that. So this one test
runs the engine at gpt2-large's width (1280, 20 heads; two layers, a
vocabulary of 8192), where the real limit means what it means on the chip."""

from benchmark_testlib import cpu_device, tiny_root

from benchmarks import harness

SEED = 2**31 + 13


def test_the_int8_control_and_an_altered_token_read_over_the_limit(tmp_path):
    """One sound window; then the reference's int8 pass in the program's
    place is read by the same number against the cell's limit (and one token
    altered in each sampled request by the widest gap, which the limit on
    the mean does not promise to catch)."""
    root = tiny_root(tmp_path)
    m = harness.load_manifest(root)
    cell = harness.Cell(root, m, "wide-serve")
    ctx = harness.Context(cell, SEED, 0.6, harness.Tracer(root, False), cpu_device())
    session = harness.load_module(root, m, "drivers", "lm_serve").setup(ctx)
    session.run_window()
    session.release()
    r = session.readings(control=True)
    limit = cell.workload["limits"]["token_logit_gap_mean"]
    assert limit == harness.load_json(
        harness.ROOT, harness.load_manifest(), "workloads", "gpt2l-serve-chat")["limits"]["token_logit_gap_mean"]
    assert r["program"]["token_logit_gap_mean"] <= limit and r["served_tokens"] >= 100
    assert r["control_int8"]["token_logit_gap_mean"] > limit
    assert r["fault_altered_token"]["token_logit_gap_max"] > 10 * r["program"]["token_logit_gap_max"]
