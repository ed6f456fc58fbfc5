"""Kernel-schedule metrics: the paper's fold applied to grids.

  * folded vs naive causal-attention grid slots (kernels/folded_attention):
    slots = executed MXU block-steps per (batch, head) -- the structural
    2x win, exact, no hardware needed.

Also times both attention schedules in interpret mode at a small shape as a
sanity check that they compute identical outputs (asserted in tests).
"""
from __future__ import annotations

from repro.kernels import folded_attention as fa


def attention_slots(seqs=(2048, 4096, 8192, 32768), bq=256):
    rows = []
    for S in seqs:
        naive = fa.grid_slots(S, bq, "naive")
        folded = fa.grid_slots(S, bq, "folded")
        rows.append({"S": S, "bq": bq, "naive": naive, "folded": folded,
                     "ratio": naive / folded})
    return rows


def main(fast=False):
    print("# kernel_schedule: paper-P3 fold applied to kernel grids")
    print("## causal attention grid slots per (batch, head)")
    print("S,bq,naive_slots,folded_slots,ratio")
    for r in attention_slots():
        print(f"{r['S']},{r['bq']},{r['naive']},{r['folded']},"
              f"{r['ratio']:.3f}")
    return True


if __name__ == "__main__":
    main()
