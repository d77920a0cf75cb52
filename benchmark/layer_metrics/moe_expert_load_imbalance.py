"""How unevenly the router fills the held experts: over the last training
pass of the window, the pairs on the busiest held expert
(``moe_expert_pairs_max``) over the mean pairs a held expert
(``moe_pairs_held_total`` of the pass / experts held), averaged over the
layers.  1.0 is an even load; the grouped products take as long as their
rows, so the sum decides their time, and the busiest expert decides how
many row tiles one group spans."""

NAME = "moe_expert_load_imbalance"
UNIT = "ratio"
LAYER = "expert layer"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "train_samples_per_s"


def read(record):
    moe = record.get("moe")
    if not moe or not moe.get("last_pass_pairs_held"):
        return None
    ratios = [
        peak / (held / moe["experts_held"])
        for peak, held in zip(moe["last_pass_pairs_max"],
                              moe["last_pass_pairs_held"]) if held]
    return sum(ratios) / len(ratios) if ratios else None
