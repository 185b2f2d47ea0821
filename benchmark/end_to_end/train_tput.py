"""``train_tput``: images through a full step (forward, backward, gradient
average, update) per second per chip, over all the steps and all the time of
the window, which ends when the last step's outputs are ready.  Host clock."""


def read(rec: dict):
    return rec["items"] / rec["window_s"] / rec["chips"]
