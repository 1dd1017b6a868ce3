"""Problem kinds, one module per ``problem`` named in a traffic file.

Each module turns a generated graph and a traffic file into what the timed
path solves, and judges the answers against the host reference:

* ``edge_values(graph, traffic)``: the edge values the program's graph holds.
* ``problem(traffic)``: the program's ``repro.solve.Problem``.
* ``draws(graph, traffic, seed)``: an endless iterator of ``(label, x0)``,
  one per solve; equal labels have equal references.
* ``reference(pool, label, traffic, control)``: a future of the
  reference answer for ``label`` (``bench.reference.ReferencePool``).
* ``compare(answers, refs, traffic)``: ``{number: (value, limit)}`` over the
  compared solves, ``answers`` and ``refs`` in the same order.
"""
