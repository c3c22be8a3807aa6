"""NCF training entrypoint (reference parity: examples/rec/run_hetu.py —
same CLI surface: --val HR@10/NDCG@10 retrieval eval, --comm for
None/PS/Hybrid, --bsp/--cache/--bound PS knobs, --all for the full
dataset).  The embedding tables are the PS sparse parameters; Hybrid
runs them through the HBM device cache while the MLP tower rides
AllReduce — the reference's canonical Hybrid workload (hybrid_ncf.sh).

    python examples/rec/run_hetu.py --val --timing
    heturun -c settings/local_ps.yml python examples/rec/run_hetu.py \
        --comm PS --timing
"""
import argparse
import math
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import hetu_tpu as ht                               # noqa: E402
from hetu_tpu.models.ncf import neural_mf           # noqa: E402
from movielens import getdata                       # noqa: E402


def hit_ratio(ranklist, gt_item):
    return int(gt_item in ranklist)


def ndcg(ranklist, gt_item):
    for i, item in enumerate(ranklist):
        if item == gt_item:
            return math.log(2) / math.log(i + 2)
    return 0.0


def ensure_local_ps():
    if os.environ.get("HETU_PS_PORTS"):
        return
    from hetu_tpu.ps import server as ps_server
    from hetu_tpu.ps import client as ps_client
    port = ps_server.pick_free_port()
    os.environ["HETU_PS_PORTS"] = str(port)
    os.environ["HETU_PS_HOSTS"] = "127.0.0.1"
    ps_server.ensure_server(port=port, nworkers=1)
    ps_client.set_default_client(ps_client.PSClient(rank=0, nworkers=1))


def worker(args):
    if args.comm in ("PS", "Hybrid"):
        ensure_local_ps()

    train, test, num_users, num_items = getdata(args.dataset)
    train_users = train["user_input"]
    train_items = train["item_input"]
    train_labels = train["labels"].astype(np.float32).reshape(-1, 1)
    if not args.all:   # reference default: first 1,024,000 samples
        cap = min(len(train_labels), 1_024_000)
        train_users, train_items, train_labels = (
            train_users[:cap], train_items[:cap], train_labels[:cap])
    if num_users is None:
        # test rows are indexed by user id; test cells are item ids
        num_users = int(max(train_users.max() + 1, test.shape[0]))
        num_items = int(max(train_items.max(), test.max()) + 1)
    test_user_input = np.repeat(
        np.arange(test.shape[0], dtype=np.int32), 100)
    test_item_input = test.reshape(-1).astype(np.int32)

    batch = args.batch_size
    topk = 10
    # score eval_users users' 100 candidates per dispatch: the reference
    # runs one user per step (run_hetu.py:44-61), i.e. num_users tiny
    # host-dispatched programs — batching users changes nothing
    # numerically (the model is pointwise over [B] ids)
    eval_batch = 100 * args.eval_users
    # drop_last=False: every user gets scored (the tail batch stays a
    # multiple of 100 because the total and eval_batch both are)
    user_input = ht.dataloader_op([
        ht.Dataloader(train_users, batch, "train"),
        ht.Dataloader(test_user_input, eval_batch, "validate",
                      drop_last=False)])
    item_input = ht.dataloader_op([
        ht.Dataloader(train_items, batch, "train"),
        ht.Dataloader(test_item_input, eval_batch, "validate",
                      drop_last=False)])
    y_ = ht.dataloader_op([
        ht.Dataloader(train_labels, batch, "train")])

    embed_ctx = ht.cpu(0) if args.comm in ("PS", "Hybrid") else None
    loss, y, train_op = neural_mf(
        user_input, item_input, y_, num_users, num_items,
        learning_rate=args.learning_rate, embed_ctx=embed_ctx)

    kwargs = {}
    if args.comm in ("PS", "Hybrid"):
        kwargs = dict(cstable_policy=args.cache, bsp=args.bsp,
                      cache_bound=args.bound)
    executor = ht.Executor({"train": [loss, train_op], "validate": [y]},
                           comm_mode=args.comm, **kwargs)

    def validate():
        hits, ndcgs = [], []
        nbatches = executor.get_batch_num("validate")
        done = 0
        for _ in range(nbatches):
            pred = executor.run("validate",
                                convert_to_numpy_ret_vals=True)[0]
            nu = len(pred) // 100
            scores = pred.reshape(nu, 100)
            items = test_item_input[done:done + nu * 100].reshape(nu, 100)
            done += nu * 100
            # rank each user's 100 candidates; col 0 is the held-out item
            order = np.argsort(-scores, axis=1)[:, :topk]
            for u in range(nu):
                ranklist = items[u, order[u]].tolist()
                hits.append(hit_ratio(ranklist, int(items[u, 0])))
                ndcgs.append(ndcg(ranklist, int(items[u, 0])))
        return float(np.mean(hits)), float(np.mean(ndcgs))

    results = {}
    start = time.time()
    for ep in range(args.nepoch):
        ep_st = time.time()
        train_loss = []
        nbatch = executor.get_batch_num("train")
        if args.metrics_every_step:
            for _ in range(nbatch):
                loss_val = executor.run(
                    "train", convert_to_numpy_ret_vals=True)
                train_loss.append(float(loss_val[0]))
        else:
            kblock = min(args.block_steps, nbatch)
            done = 0
            while done < nbatch:
                k = min(kblock, nbatch - done)
                out = executor.run_batches([{}] * k, name="train")
                done += k
                # first asnumpy syncs the block; the rest read slices of
                # the already-materialized stacked output
                train_loss.extend(
                    float(np.mean(o[0].asnumpy())) for o in out)
        ep_time = time.time() - ep_st
        msg = f"epoch {ep}: train_loss {np.mean(train_loss):.4f}"
        if args.val:
            hr, nd = validate()
            msg += f", HR@{topk} {hr:.4f}, NDCG@{topk} {nd:.4f}"
            results.update(hr=hr, ndcg=nd)
        if args.timing:
            sps = nbatch * batch / ep_time
            msg += f", train_time {ep_time:.2f}s ({sps:.0f} samples/sec)"
            results.update(samples_per_sec=sps)
        print(msg, flush=True)
        results.update(loss=float(np.mean(train_loss)))
    print(f"all time: {time.time() - start:.2f}s", flush=True)
    executor.close()
    return results


def parse_args(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--val", action="store_true",
                        help="HR@10/NDCG@10 retrieval eval per epoch")
    parser.add_argument("--all", action="store_true",
                        help="use the full train set (default 1,024,000)")
    parser.add_argument("--comm", default=None,
                        help="None / PS / Hybrid")
    parser.add_argument("--bsp", action="store_true")
    parser.add_argument("--cache", default="Device",
                        help="Device (HBM cache) / LRU / LFU / LFUOpt")
    parser.add_argument("--bound", type=int, default=100)
    parser.add_argument("--dataset", default="ml-25m")
    parser.add_argument("--batch-size", type=int, default=1024)
    parser.add_argument("--learning-rate", type=float, default=0.01)
    parser.add_argument("--nepoch", type=int, default=7)
    parser.add_argument("--timing", action="store_true")
    parser.add_argument("--eval-users", type=int, default=50,
                        help="users scored per validation dispatch")
    parser.add_argument("--metrics-every-step", action="store_true",
                        help="host-sync the loss every step (reference "
                             "loop); default uses compiled scan blocks")
    parser.add_argument("--block-steps", type=int, default=50)
    return parser.parse_args(argv)


if __name__ == "__main__":
    worker(parse_args())
