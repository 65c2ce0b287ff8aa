"""One test of the benchmark's own is expected to fail, and says why.

``tests/test_benchmark_json.py::test_cells_and_configurations`` ends on
``hidden_size == num_attention_heads * head_dim``, an invariant of dense
decoders. ``nemotron3-nano-30b-ep2-d16`` (PR 33) carries its source's
published widths: hidden 2,688, 32 heads of 128. The file is the accepted
benchmark's, which the PR that brought the configuration may not edit, and a
``model_config`` PR without its configuration is refused. The mark is strict:
when a ``benchmark`` PR repairs that line the test passes, the mark fails, and
this file goes. Until then
``tests/test_nemotron_h_bench.py::test_the_cell_is_entered_and_one_line_of_the_benchmarks_own_refuses_its_widths``
shows that the file fails at that line alone, on that configuration alone.
"""
import pytest

REFUSED = "test_benchmark_json.py::test_cells_and_configurations"
WHY = ("line 75 holds hidden_size == num_attention_heads * head_dim, a dense "
       "decoder's invariant; nemotron3-nano-30b-ep2-d16 has the published "
       "2,688 against 32 x 128: PERF.md section 7, PR 33 (0)")


def pytest_collection_modifyitems(items):
    for item in items:
        if item.nodeid.endswith(REFUSED):
            item.add_marker(pytest.mark.xfail(
                reason=WHY, raises=AssertionError, strict=True))
