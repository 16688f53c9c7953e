"""Output tokens delivered between the two reads of the engine's
counters over the decode steps reaped between them: the mean number of
slots a decode step served (first tokens come from prefill, so a full
batch reads a little over num_slots).
source: program_counter (stats()["steps"]) and client stamps."""


def read(obs):
    c = obs.get("counters")
    if not c or not c.get("steps"):
        return None
    return c["out_tokens_between_stats"] / c["steps"]
