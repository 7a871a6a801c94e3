"""Output tokens emitted in the window, over the window's seconds."""


def read(run):
    return run.output_tokens() / run.seconds
