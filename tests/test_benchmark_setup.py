"""The set-up books' reader has its tests with the benchmark
(``benchmark/tests/test_setup_records.py``); they run here too, so that the
tier-1 suite holds the reader, its four metric files and a whole CPU run of
a tiny serving cell and of the tiny train cell that reads all four."""

import dataclasses

from benchmark import spec

from benchmark.tests.test_setup_records import (  # noqa: F401
    fresh_hub,
    test_a_program_that_keeps_no_books_reads_none,
    test_all_four_read_in_the_tiny_serving_cell_and_close,
    test_all_four_read_in_the_tiny_train_cell,
    test_each_metric_lists_the_six_cells_and_one_layer,
    test_nothing_built_after_the_window_opened_is_counted,
    test_the_serving_books_close_on_warmup_and_first_requests,
    test_the_train_books_leave_out_the_harness_thread_and_the_reference,
)


def the_cells_own_metrics(monkeypatch):
    """A cell's own test counts the per-layer metrics its PR brought, each
    named after the cell. The set-up layer's four (PR 44) are every cell's
    and carry no cell's name: such a test loads its cell without them. (The
    tests are the benchmark's files, which only a ``benchmark`` PR may edit:
    PERF.md section 7.)"""
    load_cell = spec.load_cell

    def without_setup(name):
        cell = load_cell(name)
        return dataclasses.replace(cell, per_layer=[
            m for m in cell.per_layer if m["moves"] != "setup_s"])

    monkeypatch.setattr(spec, "load_cell", without_setup)
