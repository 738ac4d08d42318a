"""The new cell's own tests live with the benchmark
(``benchmark/tests/test_cohere2_cell.py``); they run here too, so that the
tier-1 suite holds the configuration, the cell and a whole CPU run of it,
and with them the near-tie rule that decides the cell's ``correct``
(``benchmark/tests/test_near_tie.py``, PR 42: one rule for both sparse
references)."""

from benchmark.tests.test_cohere2_cell import (  # noqa: F401
    test_configuration_and_cell_load_as_the_issue_states,
    test_serve_sound_then_token_altered,
)
from benchmark.tests.test_near_tie import (  # noqa: F401
    test_a_held_expert_near_the_edge_at_any_rank_is_left_out,
    test_the_first_layer_takes_its_own_threshold,
    test_the_rag_cells_finding_at_the_tiny_size,
)
