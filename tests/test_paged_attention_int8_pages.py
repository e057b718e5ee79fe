"""The int8 paged-attention kernel reads only the pages a row HAS and, under
a step's mask, only the LIVE rows' (serving/paged_attention_int8.py), with
ONE query row a slot: the cases, the poisoned pool and the two bodies are
tests/paged_int8_cases.py's (read its head before adding a case); the same
bodies run under four query rows and under the tree form in
tests/test_paged_attention_int8_pages_q_rep4.py and ..._pages_tree.py, a
file a form so that a form's traces are shared and the workers share the
forms.
"""

import pytest

from paged_int8_cases import (
    LENGTHS, MASKED, WALKS,
    a_page_past_a_rows_last_is_neither_copied_nor_multiplied,
    an_idle_row_is_never_asked_for_and_reads_zeros)


@pytest.mark.parametrize("split_kv", [False, True])
@pytest.mark.parametrize("form", ["q_rep1"])
@pytest.mark.parametrize("case", list(LENGTHS))
def test_a_page_past_a_rows_last_is_neither_copied_nor_multiplied(
        case, form, split_kv):
    a_page_past_a_rows_last_is_neither_copied_nor_multiplied(
        case, form, split_kv)


@pytest.mark.parametrize("split_kv", [False, True])
@pytest.mark.parametrize("form", ["q_rep1"])
@pytest.mark.parametrize("case", list(MASKED))
def test_an_idle_row_is_never_asked_for_and_reads_zeros(case, form, split_kv):
    an_idle_row_is_never_asked_for_and_reads_zeros(case, form, split_kv)


@pytest.mark.parametrize("case", list(WALKS))
def test_the_walk_serves_the_live_rows_alone(case):
    """None, one, two, five, eleven and all of twelve rows live, apart
    among idle ones (PR 53: a block is asked for AFTER the one in its
    buffer was multiplied; a look-ahead deeper than the few live rows'
    blocks and shorter than the many's)."""
    an_idle_row_is_never_asked_for_and_reads_zeros(case, "q_rep1", False)
