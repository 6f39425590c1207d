"""Engine: prompt tokens served from the prefix cache
(``prefix_tokens_saved``) over the prompt tokens the harness submitted,
in the window."""


def read(run):
    log = run.log
    sent = sum(len(r.prompt) for r in log.reqs
               if log.open_t <= r.submit_t < log.close_t)
    return None if sent == 0 else \
        100.0 * run.delta("prefix_tokens_saved") / sent
