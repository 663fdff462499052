"""Share of the window's slot-steps that teacher-forced a prompt token,
counted from the requests sent and the engine steps driven."""


def read(ctx):
    total = ctx.get("slot_steps")
    if not total:
        return None
    return 100.0 * ctx["prefill_slot_steps"] / total
