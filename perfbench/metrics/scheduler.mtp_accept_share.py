"""Share of the MTP module's drafts that the verify step accepted, %:
``hvd_tpu_gen_spec_accepted_total{mode="mtp"}`` over
``hvd_tpu_gen_spec_drafted_total{mode="mtp"}`` across the window (a
draft is counted for each live lane of a verify step that may still
emit two tokens). Where the program has no such series, nothing."""


def read(ctx):
    drafted = ctx.counter_delta('hvd_tpu_gen_spec_drafted_total{mode="mtp"}')
    accepted = ctx.counter_delta(
        'hvd_tpu_gen_spec_accepted_total{mode="mtp"}')
    if not drafted or accepted is None:
        return None
    return 100.0 * accepted / drafted
