"""Data generators, one module per configuration's ``generator`` key.

Each module exposes ``generate(config: dict, seed: int)`` returning
``(triples, terms)``: an (N, 3) int32 array of term ids and the list of
term strings, where ``terms[i]`` is the term of id ``i``.
"""
