"""A counter the program keeps on the device, as the family's ``counters()``
reads it after the window (``{name: float}``: sums over the steps run so far,
and ``steps``). ``per_step`` divides a sum by the steps; without it the counter
is given as it stands (a maximum, say). A family without counters, or one
whose counters lack ``counter``, gives nothing."""


def reduce(spec, ctx):
    read = getattr(ctx["family"], "counters", None)
    seen = read() if read else {}
    if spec["counter"] not in seen or not seen.get("steps"):
        return None
    value = seen[spec["counter"]]
    return value / seen["steps"] if spec.get("per_step") else value
