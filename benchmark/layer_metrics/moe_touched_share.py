"""model step: of the experts a plain expert decoder holds — all of them —
the share that at least one live lane's pick reached, a (decode step, expert
layer), in %: ``moe_held_touched_share``'s three counters and its arithmetic,
on the cells whose decode step READS by that list (since PR 50: the share of
the experts' bytes a step streams, so lower is better). Six lanes of top-2
over eight experts read about 78 %, thirty 100 %. An engine whose decode
windows say no picks (a program that reads every expert every step: the
counters are absent, or never move): left out."""
from benchmark import manifest

read = manifest.layer_reader("moe_held_touched_share").read
