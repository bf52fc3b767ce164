"""Mean device-idle gap between consecutive epoch programs: the host drive
between epochs (sample plan, noise draw, metric readout, dataset upload)."""


def read(ctx):
    gaps = [g for name in ctx.programs_of("trunk fwd/bwd + optimizer")
            for g in ctx.reduced.program_gaps_s.get(name, [])]
    return 1e3 * sum(gaps) / len(gaps) if gaps else None
