"""Mean max/avg PE load after balancing over the traffic's fixed span,
computed by the reference from the answers."""


def read(run):
    return run.quality.get("max_avg_load")
