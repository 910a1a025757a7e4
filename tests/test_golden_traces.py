"""Golden-trace guard: each release scenario's control-plane event
timeline must structurally match the committed dump under
``tests/golden/``.

A failure here means round sequencing, revocation handling, deadline
folding, or event emission changed.  If the change is intended,
regenerate with ``PYTHONPATH=src python scripts/golden_traces.py
--update`` and commit the new goldens; the structural diff printed on
failure (event-type deltas + first divergent event) is the review
artifact."""
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "scripts"))

from golden_traces import SCENARIOS, dump_scenario, golden_path  # noqa: E402
from trace_dump import diff_traces  # noqa: E402


@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_trace_matches_golden(name):
    path = golden_path(name)
    assert os.path.exists(path), (
        f"no golden for {name!r} — run scripts/golden_traces.py --update")
    with open(path) as f:
        golden = json.load(f)
    fresh = dump_scenario(name)
    assert diff_traces(golden, fresh, label_a="golden", label_b="fresh"), (
        f"trace for {name!r} diverged from the golden; see the structural "
        f"diff above (regenerate with scripts/golden_traces.py --update "
        f"if intended)")


def test_diff_is_clean_on_itself_and_flags_a_truncation():
    """The structural diff the goldens rest on: a trace matches itself,
    and dropping its last event is a divergence."""
    name = sorted(SCENARIOS)[0]
    with open(golden_path(name)) as f:
        trace = json.load(f)
    assert diff_traces(trace, trace)
    assert not diff_traces(trace, trace[:-1])
