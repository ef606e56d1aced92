"""Layer applications in one train step as the program counted them while the step was traced (its always-on counter `loop_layer_applications`, read around the step's lowering by
runners/ouro_train.py): depth x passes, the witness that the loop ran
every layer in every pass."""


def read(run: dict):
    return run["counters"].get("loop_layer_applications")
