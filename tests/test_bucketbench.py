"""Bucketed-vs-fixed translation batching benchmark (tools/bucketbench.py).

The empirical companion to TranslationData.bucketing_report's analytic
pricing (VERDICT r3 next #9): bucketed batching is actually implemented —
one seq2seq model variant per bucket shape sharing ONE parameter set — and
both modes train the same corpus. On the CPU the timing ratio is noise;
the test pins structure and token accounting (on-chip number: not
measured).
"""

import json

import pytest

pytestmark = pytest.mark.slow  # several shape compiles


def test_bucketbench_tool(tmp_path, capsys):
    from ddlbench_tpu.tools import bucketbench

    rc = bucketbench.main([
        "-m", "seq2seq_t", "--pairs", "192", "--batch", "8",
        "--src-len", "16", "--tgt-len", "16", "--dtype", "float32",
        "--corpus-dir", str(tmp_path), "--platform", "cpu"])
    assert rc == 0
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    by_mode = {l["mode"]: l for l in lines}
    assert set(by_mode) == {"fixed", "bucketed", "summary"}
    fixed, bucketed = by_mode["fixed"], by_mode["bucketed"]
    # same corpus: valid-token totals agree up to per-bucket batch tails
    assert abs(fixed["valid_tokens"] - bucketed["valid_tokens"]) \
        <= 0.1 * fixed["valid_tokens"]
    # bucketing buys padding efficiency and costs compiles
    assert bucketed["padding_efficiency"] > fixed["padding_efficiency"]
    assert bucketed["num_compiles"] > fixed["num_compiles"]
    assert by_mode["summary"]["analytic_efficiency_ratio"] > 1.0