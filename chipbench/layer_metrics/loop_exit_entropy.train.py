"""How spread a position's exit distribution over the passes is: the
program's counter ``exit_entropy``, mean over the steps of the counted calls,
over ``log(total_ut_steps)``, in %. At 100 % every pass is as likely an exit
as any other; at 0 % the gate has collapsed onto one pass.
Source: program_counter."""

from chipbench import ouro_reads


def read(run):
    return ouro_reads.exit_entropy_share(run)
