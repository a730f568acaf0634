"""Host seconds from the first program the cell builds to the end of its
warm-up: compilation in a cold checkout, loads from the compile cache in
a warm one."""


def read(ctx):
    return ctx.facts.get("warmup_s")
