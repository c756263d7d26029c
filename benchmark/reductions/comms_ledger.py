"""Bytes one chip hands to the interconnect per step at the call sites ``sites``,
in GB, from the program's own ledger of collectives (``monitor.comms``: kind,
axis, dtype, per-rank wire bytes and call site of every collective the library
issues, booked while the step is *traced*).

The ledger counts per trace, not per step: the number is a step's only while
the process has traced the step once, which is how the harness runs it (the
window's ``compilations`` are 0). A program without the ledger, or a cell whose
step issues no collective at these sites, gives nothing."""


def reduce(spec, ctx):
    try:
        from beforeholiday_tpu.monitor import comms_records
    except ImportError:
        return None
    wire = sum(r["bytes"] for r in comms_records() if r["site"] in spec["sites"])
    return wire / 1e9 if wire else None
