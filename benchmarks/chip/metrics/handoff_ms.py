"""Device ms per replay step in the particle handoff: the chare lookup,
the moved mask, the source and destination PE gathers and the ext/intra
byte counts (scope replay/handoff, pic/driver._chunk_runner)."""
from chipbench import marks


def read(run):
    return marks.scope_ms_per_unit(
        run, lambda p: marks.under(p, marks.HANDOFF), "steps")
