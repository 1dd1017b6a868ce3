"""Seconds of the program's first ``repro.schedule.put``: the stripes placed on the device."""

from bench import scopes


def read(run):
    puts = scopes.program_spans("repro.schedule.put")
    return puts[0].seconds if puts else None
