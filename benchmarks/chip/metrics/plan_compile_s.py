"""Host clock around the program's PIM plan compile
(``repro.models.pim.compile_pim_params``, up to ``block_until_ready``).
Part of ``setup_s``."""


def read(run):
    return run.plan_compile_s
