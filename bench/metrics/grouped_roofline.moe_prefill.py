"""The grouped expert gemm kernels' share of their roofline in the window
(%): for every prefill of the window, from its routed-rows counter, each
grouped gemm's operations ``2·Σ_e rows_e·k·n`` and bytes (the touched
experts' weights, the rows in and out) at the chip's peaks, the larger of
the two times, summed; over the summed device time of the ``grouped_gemm``
kernels' events.  None where the program keeps no routed-rows counter."""

from bench import tracing, yardstick_moe


def read(run):
    rows = run.raw.get("routed_rows")
    if not rows:
        return None
    lo, hi = run.tr.segment("bench.window")
    kernel = sum(o.end - o.start for o in run.tr.ops_in(lo, hi)
                 if tracing.op_kind(o) == "kernel:grouped_gemm") / 1e9
    if kernel <= 0:
        return None
    need = yardstick_moe.grouped_roofline_s(run.cell["config_data"], rows,
                                            run.peak)
    return 100.0 * need / kernel
