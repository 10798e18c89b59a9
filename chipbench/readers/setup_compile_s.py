"""``CompileCounter``'s seconds in the leased process at the window's
start: compilation and cache reads of set-up."""


def read(ctx):
    return ctx["before"].get("compile", {}).get("seconds")
