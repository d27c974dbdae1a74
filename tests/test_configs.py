"""The config registry: every architecture and its shapes, and the cells
each one documents as skipped."""
from repro.configs import get_arch, list_archs


def test_all_archs_registered():
    archs = list_archs()
    assert len(archs) == 11
    for a in archs:
        spec = get_arch(a)
        assert len(spec.shapes) == 4


def test_skipped_cells_documented():
    skipped = {(a, s[0]) for a in list_archs()
               for s in get_arch(a).skipped_shapes}
    assert ("qwen2.5-32b", "long_500k") in skipped
    assert ("gemma2-2b", "long_500k") not in skipped  # hybrid: runs
