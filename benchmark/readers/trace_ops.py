"""A per-layer metric from named device ops of the reduced trace: the share
of a peak that a kernel's arithmetic reached while its ops ran.

args: ``ops`` {device op name (``trace_reduce.short_name``): operations one
unit of the driver's work runs in the ops of that name}, ``peak`` the entry
of ``peaks.json`` to divide by.  The ops' seconds are those of the trace's
``device_ops`` (its ten longest names); seconds per unit are the traced
seconds scaled by units per second of the driver's window, as the ``trace``
reader scales the busy seconds, so a profile that starts or stops inside a
step does no harm.  Only the names found are counted, operations and
seconds alike: a name that fell out of the ten takes its operations with
it.  No trace, no window, or none of the names among the ten: nothing to
read.
"""


def read(args, evidence):
    t, w = evidence.get("trace"), evidence.get("window")
    if not t or not t.get("window_s") or not w or not w.get("units") \
            or not evidence.get("peaks"):
        return None
    seconds = dict(t.get("device_ops", ()))
    found = [name for name in args["ops"] if seconds.get(name)]
    if not found:
        return None
    units_traced = w["units"] / w["seconds"] * t["window_s"]
    per_unit_s = sum(seconds[name] for name in found) / units_traced
    operations = sum(args["ops"][name] for name in found)
    # the operations are one chip's; the trace's op seconds are summed over
    # the chips that ran anything
    per_unit_s /= t.get("chips", 1)
    return operations / per_unit_s / evidence["peaks"][args["peak"]] * 100.0
