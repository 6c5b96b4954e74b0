"""The four-chip tenant mesh cell with its timed path broken underneath
comes out not correct, once per fault the cell can have, and each fault
is caught by the check that names it."""

import pytest

from bench_rehearse import rehearse


@pytest.mark.parametrize("fault, failing", [
    # Replies stand; only the counters are off.
    ("tenant_count_shifted", {"tenant_counts"}),
    ("exchange_left_out", {"tenant_counts"}),
    ("answer_altered", {"wrong_replies", "unmatched_decisions"}),
    ("state_unchanged", {"wrong_replies", "unmatched_decisions",
                         "tenant_counts"}),
])
def test_a_broken_mesh_path_is_not_correct(fault, failing):
    out = rehearse("tenants-mesh4.zipf-sat", 2**31 + 23, fault=fault)
    assert out["rehearsal_correct"] is False
    failed = {name for name, v in out["checks"].items()
              if v["value"] > v["limit"]}
    assert failed == failing
