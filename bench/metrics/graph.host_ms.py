"""Host time of the graph layer per answered query, around the join: the
pattern's compilation (orienting the graph) and the post-processing of the
join's rows into occurrences (``graph.compile_pattern`` + ``graph.postprocess``
spans, summed in ``EnumerationResult.host_us``)."""


def read(run):
    us = [getattr(s.result, "host_us", None) for s in run.answered]
    if not us or None in us:
        return None
    return sum(us) / len(us) / 1e3
