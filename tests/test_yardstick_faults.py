"""The yardstick's controls and fault cases (``benchmark/tests/``), brought
under tier-1's collection by name: a control reads not-correct, and so does
a whole rehearsed run with the timed path broken underneath. The
"unbroken is correct" cases are ``tests/test_yardstick.py``'s per-cell
case. A file of its own, so that ``--dist loadfile`` can run it beside the
rehearsals and not after them.
"""

import pytest

from benchmark.tests.test_controls import (  # noqa: F401
    test_encoder_control_reads_above_the_limit,
    test_search_control_reads_above_the_limit_and_exact_below,
    test_tokenizer_matches_the_one_the_configuration_assumes,
)
from benchmark.tests.test_faults import (  # noqa: F401
    test_ingest_vector_altered_is_not_correct,
    test_search_answer_altered_is_not_correct,
)
from benchmark.tests.test_filter import (  # noqa: F401
    test_a_scan_that_drops_the_mask_is_not_correct,
    test_answers_not_exact_inside_the_filter_are_not_correct,
    test_the_filtered_control_reads_above_the_limit,
    test_the_filtered_reference_judges_its_own_answers_correct,
)
from benchmark.tests.test_hybrid import (  # noqa: F401
    test_a_broken_fuse_is_not_correct,
    test_the_control_is_not_correct,
    test_the_reference_alone_judges_its_own_answers_correct,
)
from benchmark.tests.test_live import (  # noqa: F401
    test_a_write_that_never_reaches_the_device_is_not_correct,
    test_an_acknowledgement_before_the_apply_is_not_correct,
    test_the_live_control_reads_above_the_limit,
    test_the_reference_judges_answers_as_of_their_request,
)

pytestmark = pytest.mark.usefixtures("benchmark_state_put_back")
