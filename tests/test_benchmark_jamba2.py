"""The new cell's own tests live with the benchmark
(``benchmark/tests/test_jamba2_cell.py``); they run here too, so that the
tier-1 suite holds the configuration, the cell and a whole CPU run of it."""

from benchmark.tests import test_jamba2_cell
from benchmark.tests.test_jamba2_cell import (  # noqa: F401
    test_least_bytes_of_a_tick_count_the_model_by_layer_kind,
    test_serve_sound_then_token_altered,
)
from tests.test_benchmark_setup import the_cells_own_metrics


def test_configuration_and_cell_load_as_the_issue_states(monkeypatch):
    the_cells_own_metrics(monkeypatch)
    test_jamba2_cell.test_configuration_and_cell_load_as_the_issue_states()
