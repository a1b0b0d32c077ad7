"""Device ms per replay step in the particle push: the kernel and the
corner-charge gathers of its wrapper (scope kernel/pic-push)."""
from chipbench import layers, readers


def read(run):
    return readers.scope_ms_per_step(run, lambda p: layers.PUSH in p)
