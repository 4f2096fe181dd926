"""The paper's RandomStream (§6, Figs. 7 and 8): the query's types and the
noise types, every one equally likely, no attributes."""


def types(gen):
    return list(gen["query_types"]) + list(gen["noise_types"])


def draw(s, n):
    return {"type": s.rng.integers(0, len(s.type_names), n)}
