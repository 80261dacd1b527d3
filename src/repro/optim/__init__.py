from repro.optim.adamw import AdamWState, adamw_init, adamw_update
from repro.optim.schedule import cosine_schedule, wsd_schedule, make_schedule

__all__ = ["AdamWState", "adamw_init", "adamw_update", "cosine_schedule",
           "wsd_schedule", "make_schedule"]
