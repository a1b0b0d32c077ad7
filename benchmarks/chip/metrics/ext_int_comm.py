"""External over internal communication bytes over the traffic's fixed
span, computed by the reference from the answers."""


def read(run):
    return run.quality.get("ext_int_comm")
