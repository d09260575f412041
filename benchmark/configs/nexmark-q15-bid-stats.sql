-- NEXmark Query 15 of the nexmark/nexmark Flink suite, the bidding
-- statistics report: per period, over the bid stream, the bids, the distinct
-- bidders and the distinct auctions, each in all and in three price bands,
-- with FILTER as the suite writes it. The suite groups by calendar day; here
-- the period is a 10 s tumbling window (this engine's dialect of a
-- per-period report, and the configuration's one cut). The dollar names are
-- filled from the configuration's generator settings, the traffic mix's rate
-- and --seed.
CREATE TABLE nexmark (
  "bid" BOOLEAN, "bid.auction" BIGINT, "bid.bidder" BIGINT, "bid.price" BIGINT
) WITH (
  connector = 'nexmark',
  inter_event_micros = $inter_event_micros,
  first_event_micros = $first_event_micros,
  event_rate = $event_rate,
  seed = $seed
);
CREATE TABLE bid_stats (
  ws TIMESTAMP,
  total_bids BIGINT, rank1_bids BIGINT, rank2_bids BIGINT, rank3_bids BIGINT,
  total_bidders BIGINT, rank1_bidders BIGINT, rank2_bidders BIGINT, rank3_bidders BIGINT,
  total_auctions BIGINT, rank1_auctions BIGINT, rank2_auctions BIGINT, rank3_auctions BIGINT
) WITH (connector = '$sink', type = 'sink');
INSERT INTO bid_stats
SELECT Stats.window.start,
  Stats.total_bids, Stats.rank1_bids, Stats.rank2_bids, Stats.rank3_bids,
  Stats.total_bidders, Stats.rank1_bidders, Stats.rank2_bidders, Stats.rank3_bidders,
  Stats.total_auctions, Stats.rank1_auctions, Stats.rank2_auctions, Stats.rank3_auctions
FROM (
  SELECT tumble(interval '10 seconds') AS window,
    count(*) AS total_bids,
    count(*) FILTER (WHERE "bid.price" < 10000) AS rank1_bids,
    count(*) FILTER (WHERE "bid.price" >= 10000 AND "bid.price" < 1000000) AS rank2_bids,
    count(*) FILTER (WHERE "bid.price" >= 1000000) AS rank3_bids,
    count(DISTINCT "bid.bidder") AS total_bidders,
    count(DISTINCT "bid.bidder") FILTER (WHERE "bid.price" < 10000) AS rank1_bidders,
    count(DISTINCT "bid.bidder") FILTER (WHERE "bid.price" >= 10000 AND "bid.price" < 1000000) AS rank2_bidders,
    count(DISTINCT "bid.bidder") FILTER (WHERE "bid.price" >= 1000000) AS rank3_bidders,
    count(DISTINCT "bid.auction") AS total_auctions,
    count(DISTINCT "bid.auction") FILTER (WHERE "bid.price" < 10000) AS rank1_auctions,
    count(DISTINCT "bid.auction") FILTER (WHERE "bid.price" >= 10000 AND "bid.price" < 1000000) AS rank2_auctions,
    count(DISTINCT "bid.auction") FILTER (WHERE "bid.price" >= 1000000) AS rank3_auctions
  FROM nexmark WHERE "bid" GROUP BY window
) AS Stats;
