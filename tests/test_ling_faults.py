"""Each of the faults of ``benchmarks/ling_check_faults.py`` that is put
into a mixer (KDA, MLA) of ``models/ling.py``, seen by that part
(``ling_helpers.check_fault``)."""

import pytest

from ling_helpers import FAULT_SEEN_IN, check_fault


@pytest.mark.parametrize("name", sorted(n for n, (part, _) in FAULT_SEEN_IN.items()
                                        if part in ('kda', 'mla')))
def test_each_fault_moves_the_one_part_it_is_put_into(name):
    check_fault(name)
