"""Duration of the program's ``plan:optimize`` span: the adapter probe over
up to 1,000 rows plus the rule-based optimizer."""


def read(ctx):
    durs = [s["dur"] for s in ctx["spans"] if s["name"] == "plan:optimize"]
    return sum(durs) if durs else None
