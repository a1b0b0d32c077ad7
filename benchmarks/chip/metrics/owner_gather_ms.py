"""Device ms per replay step in the exchange's owner maps, the old and
new owner of every particle's chare, computed on every step and read on
a fired one (scope replay/owners, pic/driver._chunk_runner)."""
from chipbench import marks


def read(run):
    return marks.scope_ms_per_unit(
        run, lambda p: marks.under(p, marks.OWNERS), "steps")
