"""The mean probability of leaving at the LAST exit: the program's counters `exit_mass_micro_p<last>` / (1e6 x `exit_probe_tokens`), filled by its probe `exit_stats` in set-up on the seed's state and the first
batch (runners/ouro_train.py). The witness that the gates are live:
three gates at 0.5 leave 0.125 for the last exit, gates stuck shut
leave 1.0, stuck open 0."""


def read(run: dict):
    tokens = run["counters"].get("exit_probe_tokens")
    mass = run["counters"].get("exit_last_pass_mass_micro")
    if not tokens or mass is None:
        return None
    return mass / 1e6 / tokens
