"""The int8 paged-attention kernel's page and idle-row tests
(tests/paged_int8_cases.py's bodies; tests/test_paged_attention_int8_pages.py
says what they hold) with FOUR query rows a slot.
"""

import pytest

from paged_int8_cases import (
    LENGTHS, MASKED,
    a_page_past_a_rows_last_is_neither_copied_nor_multiplied,
    an_idle_row_is_never_asked_for_and_reads_zeros)


@pytest.mark.parametrize("split_kv", [False, True])
@pytest.mark.parametrize("form", ["q_rep4"])
@pytest.mark.parametrize("case", list(LENGTHS))
def test_a_page_past_a_rows_last_is_neither_copied_nor_multiplied(
        case, form, split_kv):
    a_page_past_a_rows_last_is_neither_copied_nor_multiplied(
        case, form, split_kv)


@pytest.mark.parametrize("split_kv", [False, True])
@pytest.mark.parametrize("form", ["q_rep4"])
@pytest.mark.parametrize("case", list(MASKED))
def test_an_idle_row_is_never_asked_for_and_reads_zeros(case, form, split_kv):
    an_idle_row_is_never_asked_for_and_reads_zeros(case, form, split_kv)


@pytest.mark.parametrize("case", ["walk_of_5_of_12"])
def test_the_walk_serves_the_live_rows_alone(case):
    """tests/test_paged_attention_int8_pages.py's, in this file's form at
    one count: five live rows' eight blocks through five buffers."""
    an_idle_row_is_never_asked_for_and_reads_zeros(case, "q_rep4", False)
