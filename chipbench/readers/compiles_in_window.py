"""Programs the leased process asked the compiler (or its cache) for
inside the window; expected 0."""


def read(ctx):
    a, b = ctx["after"].get("compile"), ctx["before"].get("compile")
    if not a or not b:
        return None
    return a["programs"] - b["programs"]
