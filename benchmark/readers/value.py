"""A number the run already holds, named by a dotted path: the generator's
own statistics (`generator.commit_p95_ms`) or a counter's difference over the
window (`sources.counters.full_repacks`)."""


def read(params: dict, result: dict):
    node = result
    for part in params["path"].split("."):
        if not isinstance(node, dict) or part not in node:
            return None
        node = node[part]
    return float(node)
