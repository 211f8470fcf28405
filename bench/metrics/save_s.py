"""Mean seconds of one partial save in the window: the controller's own
``stats["save_seconds"]`` (selection and scatter, after
``block_until_ready``) over ``stats["saves"]``, both as moved by the
window."""


def read(ctx):
    if not ctx["saves"]:
        return None
    return ctx["save_seconds"] / ctx["saves"]
