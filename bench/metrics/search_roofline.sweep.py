"""The cost evaluator's share of its roofline (``core/cost_model.py`` or
``kernels/fusion_eval.py``): the least time the chip could take for one
call's C x population x (generations + 1) evaluations (``bench/work.py``;
the larger of operations over the bf16 peak and bytes over the memory
bandwidth, which bounds it) over the device time of one ``_ga_grid`` call.
The repair rounds and the seeding search are left out of the work, so the
share is a lower bound of the evaluator's, and reads the same work
whichever evaluator runs."""
from bench.trace import module_time


def read(rec):
    secs, calls = module_time(rec.trace, "jit__ga_grid")
    if not calls:
        return None
    least = max(rec.eval_ops / rec.peaks["bf16_flops_per_s"],
                rec.eval_bytes / rec.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (secs / calls)
