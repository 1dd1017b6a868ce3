"""Graph generators, one module per ``generator`` named in a config file.

Each module has ``generate(config, seed) -> bench.graphgen.SymmetricGraph``.
"""
