"""All images of all steps completed in the window, over the whole window's
seconds (first batch handed over to ``train_epoch``'s return), per chip."""


def read(run: dict):
    win = run["window"]
    return win["images"] / win["seconds"] / run["chips"]
