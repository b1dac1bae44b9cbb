from repro.core.exchanger import (Exchanger, EXCHANGERS, get_exchanger,
                                  default_chunk_sum, make_rs_plan,
                                  param_wire_dtype)
from repro.core.bsp import (make_bsp_step, init_train_state,
                            init_sharded_train_state)
from repro.core.easgd import make_async_step, init_async_state
from repro.core.gspmd import make_gspmd_step, fsdp_state_shardings
