"""Self seconds of the program's first ``repro.schedule.build``: the host stripe build.

The span's length less that of its ``repro.schedule.put`` child.
"""

from bench import scopes


def read(run):
    builds = scopes.program_spans("repro.schedule.build")
    if not builds:
        return None
    b = builds[0]
    puts = [
        p for p in scopes.program_spans("repro.schedule.put")
        if p.parent == b.name and b.start_ns <= p.start_ns and p.end_ns <= b.end_ns
    ]
    return b.seconds - sum(p.seconds for p in puts)
